"""How unevenly a decode step loads the routed experts held here: the
busiest held expert's rows over the mean held expert's, from the ring's
per-step counter ``decode_held_expert_tokens`` (rows each held expert
received in the decode program, summed over the MoE layers), the mean
over the window's steps that decoded. 1 is an even load; the grouped
matmul's time follows the experts TOUCHED (each read once) more than
the rows, so this says how far the routing is from the even load the
sizing assumes, not how slow the step is. None where the ring has no
such counter (every family but the dropless router's)."""

from benchmarks.lib.step_ring import window_records


def read(ctx):
    records = window_records(ctx)
    if not records:
        return None
    ratios = []
    for r in records:
        rows = r.get("attrs", {}).get("decode_held_expert_tokens")
        if r["decoding"] and rows and sum(rows) > 0:
            ratios.append(max(rows) * len(rows) / sum(rows))
    return sum(ratios) / len(ratios) if ratios else None
