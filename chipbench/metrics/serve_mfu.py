"""Model layer: the traced window's model FLOPs over its length times the
chip's bf16 peak (``peaks.json``), in %.

The FLOPs are those a server without prefix reuse must run for the work
the window completed: each prefill's prompt through every layer with
causal attention and the output head at its last position, and each
generated token through every layer and the output head with attention
over its cached context (the cell's reference module, ``flops_prompt``
and ``flops_decode``; 2 per parameter per token as the program's
``launch/roofline.py`` counts).  Work that completed after the profiler
stopped is left out, as is the window's time after it."""


def read(ctx: dict) -> float | None:
    peak = ctx["peaks"].get("bf16_flops_per_s")
    if not peak or ctx["busy_s"] <= 0 or ctx["window_s"] <= 0:
        return None
    m, spec = ctx["model"], ctx["spec"]
    flops = sum(m.flops_prompt(spec, n) for n in ctx["prefills"])
    for b, c in ctx["steps"]:
        # b requests over c cached tokens in all: attention is linear in
        # the context, so the batch's sum is one token's cost times b
        # plus the pairs of the summed context
        flops += b * m.flops_decode(spec, 0) + (
            m.flops_decode(spec, c) - m.flops_decode(spec, 0))
    return 100.0 * flops / (ctx["window_s"] * peak)
