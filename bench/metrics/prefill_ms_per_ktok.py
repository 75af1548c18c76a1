"""Device time of prefill per thousand prompt tokens: the prefill
programs' device time in the traced window over the prompt tokens the
engine prefilled there (model layer, ``models/transformer.py``)."""

PREFILL_PROGRAM = "jit_prefill_fn"


def read(rec):
    tr, host = rec["trace"], rec["host"]
    if not tr or not host or not host["prompt_tokens"]:
        return None
    t = sum(v for k, v in tr["programs"].items() if k == PREFILL_PROGRAM)
    if t <= 0:
        return None
    return 1e6 * t / host["prompt_tokens"]
