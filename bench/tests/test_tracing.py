"""Attribution of decode steps to requests: a request that finished
after its n-th answer token at decode step s decoded in steps
s - n + 2 .. s, attending over P + 1 .. P + n - 1 positions."""
import tracing


def recorder(records):
    r = object.__new__(tracing.Recorder)
    r.records = records
    return r


def test_decode_steps_counted_back_from_the_finish():
    r = recorder([
        {"P": 10, "n": 4, "end_step": 5},    # steps 3, 4, 5
        {"P": 100, "n": 2, "end_step": 4},   # step 4
        {"P": 7, "n": 1, "end_step": 9},     # no decode step
        {"P": 50, "n": 3, "end_step": None},  # never finished: no record
    ])
    ctx, toks = r._decode_work(0, 10)
    assert toks == 3 + 1
    assert ctx == (11 + 12 + 13) + 101
    # only steps 4 and 5 inside (3, 5]
    ctx, toks = r._decode_work(3, 5)
    assert toks == 2 + 1
    assert ctx == (12 + 13) + 101
    assert r._decode_work(5, 8) == (0, 0)
