"""A small configuration and traffic for runs of the whole harness on the
CPU: the cells' own code paths at a size a test can hold."""
TINY = {"hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "num_hidden_layers": 2, "vocab_size": 512}


def config(deployment):
    dep = dict(deployment, max_len=512)
    dep["islands"] = [dict(s, capacity_units=4.0) if s.get("holds_chip")
                      else s for s in deployment["islands"]]
    return dict(TINY, deployment=dep)


def traffic(shared_max=96):
    return {"arrival": {"kind": "poisson", "rate_per_s": 3.0},
            "prompt": {"min": 100, "max": 300, "alpha": 1.1},
            "output": {"min": 8, "max": 24, "alpha": 1.4},
            "shared": {"corpus": 2, "min": 64, "max": shared_max,
                       "alpha": 1.1, "zipf_s": 1.1, "reuse_p": 0.8},
            "warmup": {"requests": 4, "seed": 7, "max_new": 4,
                       "route_pools": [1, 2, 4]}}
