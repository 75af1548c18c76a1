"""Share of the window's overlay requests served from an already stacked
expert set (``swap_summary()`` stack_hits / (stack_hits + stack_builds),
counted from the window's start; expert tiers, ``serve/expert_cache.py``)."""


def read(rec):
    s, s0 = rec["engine"], rec["engine0"]
    hits = s.get("stack_hits", 0) - s0.get("stack_hits", 0)
    builds = s.get("stack_builds", 0) - s0.get("stack_builds", 0)
    if hits + builds == 0:
        return None
    return 100.0 * hits / (hits + builds)
