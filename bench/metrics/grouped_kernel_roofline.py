"""The grouped ternary kernel's share of its roofline: the least time the
chip needs for the calls' useful work (``kernel_cost.grouped_ternary``:
each row against its own expert once) over the calls' device time
(kernels layer, ``kernels/ternary_matmul.py``)."""

from bench import kernel_cost


def read(rec):
    tr = rec["trace"]
    calls = [c for c in (tr or {}).get("grouped_kernel", [])
             if c["shape"] is not None]
    if not calls:
        return None
    ideal = 0.0
    spent = 0.0
    for c in calls:
        m, k, n, e, _ = c["shape"]
        t, _ = kernel_cost.ideal_seconds(
            kernel_cost.grouped_ternary(m, k, n, e), rec["peaks"])
        ideal += t
        spent += c["seconds"]
    return 100.0 * ideal / spent if spent > 0 else None
