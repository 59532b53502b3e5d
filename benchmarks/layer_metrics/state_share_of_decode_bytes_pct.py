"""Share of a decode step's least bytes that is recurrent state, %: the
ring's per-step counter ``state_bytes`` (what the step's programs read
and wrote of per-slot state, as the engine counts it) over its sum with
the parameters once and the attention layers' cache (the terms of
lib/hybrid_bytes.decode_step_bytes), over the window's steps that decoded and
prefilled nothing — a step that also ran a prefill program reads the
parameters twice, and is left out. Says how much of the step the
mechanism is — higher is more of it, not better. None where the engine
keeps no recurrent state or its ring has no such counter."""

from benchmarks.lib.step_ring import ring_static, window_records


def read(ctx):
    records = window_records(ctx)
    param_bytes, kv_bytes = (ring_static(k) for k in (
        "param_bytes", "kv_bytes_per_token"))
    if not records or not param_bytes or not kv_bytes:
        return None
    decoded = [r for r in records
               if r["decoding"] and not r["prefill_tokens"]
               and r.get("state_bytes")]
    if not decoded:
        return None
    state = sum(r["state_bytes"] for r in decoded)
    return 100.0 * state / (
        len(decoded) * param_bytes
        + sum(r["context_tokens"] for r in decoded) * kv_bytes + state)
