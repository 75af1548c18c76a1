"""Device time of one decode step: the decode-chunk programs' device time
in the traced window over the decode steps they ran (model layer,
``serve/decode_loop.py``)."""

CHUNK_PROGRAM = "jit_run"


def read(rec):
    tr, host = rec["trace"], rec["host"]
    if not tr or not host or not host["decode_steps"]:
        return None
    t = sum(v for k, v in tr["programs"].items() if k == CHUNK_PROGRAM)
    if t <= 0:
        return None
    return 1e3 * t / host["decode_steps"]
