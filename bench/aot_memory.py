"""Compile a configuration's fused serving steps at the cell's sizes for a
described TPU v5e, without a chip, and print what the compiler says they
need (``memory_analysis()``), beside the weights and the page pool.

    JAX_PLATFORMS=cpu python3 bench/aot_memory.py qwen3-4b [rows ...]

It rehearses a cell's sizing before the chip: a step that does not fit is
refused here. The shapes are the largest the batcher dispatches: every
slot decoding over the whole context, and a fused prefill of ``rows``
runs of one token budget each over the whole context.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import counts
    from repro.models.model import get_model
    from repro.models.steps import (make_fused_decode_step,
                                    make_fused_prefill_step)
    from run import program_config

    jax.config.update("jax_enable_compilation_cache", False)
    name = argv[0]
    rows_list = [int(r) for r in argv[1:]] or [1, 4]
    cfg = json.load(open(BENCH / "configs" / f"{name}.json"))
    dep = cfg["deployment"]
    pc = program_config(cfg)
    model = get_model(pc)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), model.abstract())
    slots = int(round(sum(s.get("capacity_units", 0)
                          for s in dep["islands"] if s.get("holds_chip"))
                      * dep["slots_per_capacity_unit"]))
    ps, max_len = 16, dep["max_len"]
    W = max_len // ps
    P = slots * W + 1
    w = counts.widths(cfg)
    kv = sds((w["layers"], P, ps, w["hkv"], w["hd"]), jnp.bfloat16)
    pool = {"blocks": {"slot0": {"mixer": {"k_pages": kv, "v_pages": kv}}}}
    i32 = jnp.int32
    print(f"{name}: weights {counts.weight_bytes(cfg) / 1e9:.3f} GB, pool "
          f"{2 * kv.size * 2 / 1e9:.3f} GB ({slots} slots x {max_len} tokens,"
          f" {P} pages)", flush=True)
    dec = jax.jit(make_fused_decode_step(model), donate_argnums=(1,))
    c = dec.lower(params, pool, sds((slots,), i32), sds((slots,), jnp.bool_),
                  sds((slots,), i32), sds((slots,), i32), sds((slots, W), i32),
                  sds((slots,), i32), sds((slots,), i32),
                  sds((slots, max_len), i32)).compile()
    print(f"  fused decode, {slots} slots x {W} pages: "
          f"{c.memory_analysis()}", flush=True)
    pre = jax.jit(make_fused_prefill_step(model), donate_argnums=(1,))
    for r in rows_list:
        c = pre.lower(params, pool, sds((r, 4 * ps), i32), sds((r,), i32),
                      sds((r, W), i32), sds((r, 4), i32), sds((r,), i32),
                      sds((r,), i32), sds((r,), i32), sds((slots,), i32),
                      sds((slots, max_len), i32)).compile()
        print(f"  fused prefill, {r} rows x 64 tokens x {W} pages: "
              f"{c.memory_analysis()}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
