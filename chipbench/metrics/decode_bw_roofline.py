"""Kernels layer, the decode step: its share of the HBM bandwidth
roofline, in %.

The least bytes one decode step must read are the weights once and each
active request's cached keys and values (the cell's reference module,
``weight_bytes`` and ``kv_bytes_per_token``); summed over the traced
steps and divided by the chip's HBM bandwidth (``peaks.json``), that is
the least time.  The share is the least time over the device time spent
inside the decode steps' spans (``MultiTenantEngine._decode_batch``, ECI
rebalances nested in them left out): the step program, the scatter of
the new keys and values into the pool, and the token fetch.  A decode
step does a few FLOPs per byte, so bandwidth bounds it."""


def read(ctx: dict) -> float | None:
    bw = ctx["peaks"].get("hbm_bytes_per_s")
    if not bw or ctx["decode_busy_s"] <= 0 or not ctx["steps"]:
        return None
    m, spec = ctx["model"], ctx["spec"]
    least = sum(m.weight_bytes(spec) + c * m.kv_bytes_per_token(spec)
                for _, c in ctx["steps"])
    return 100.0 * least / bw / ctx["decode_busy_s"]
