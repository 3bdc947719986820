"""Record the small trace that ``test_reduce.py`` reads (run on a chip).

    python3 chipbench/tests/record_trace.py <cell> <seed> <out.json.gz>

Runs the cell's warm-up, then two windows under the profiler with the
benchmark's spans, and keeps the host spans of the second window and its
device operations of ``MIN_NS`` or longer, as JSON event tuples (see
``reduce.py``).  (The RO token replays' loop bodies add about a million
short operations a window; they are left out to keep the file small.)
"""
import gzip
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run as RUN  # noqa: E402
import cell as C  # noqa: E402
import reduce  # noqa: E402
import tracing  # noqa: E402

KEEP = [tracing.WINDOW_SPAN, "simulate_many", "analyze_windows",
        "partition_fn"]
MIN_NS = 20_000


def main(name: str, seed: int, out: str) -> None:
    jax = RUN.setup_jax()
    cell = C.Cell(name)
    RUN.require_chips(int(cell.entry["chips"]))
    spans = tracing.Spans()
    run = C.Run(cell, seed, tracing.CompileCounter(), **spans.install())
    run.setup(0.0)
    tmp = RUN.TRACE_DIR
    shutil.rmtree(tmp, ignore_errors=True)
    run.annotate = tracing.WINDOW_SPAN
    jax.profiler.start_trace(str(tmp),
                             profiler_options=tracing.profiler_options())
    run.step(run.mgr)
    run.step(run.mgr)
    jax.profiler.stop_trace()
    events = tracing.read_events(tmp, KEEP)
    shutil.rmtree(tmp, ignore_errors=True)
    wins = sorted(s for s in reduce.host_spans(events, [tracing.WINDOW_SPAN]))
    _, lo, hi = wins[-1]
    keep = [e for e in events if lo <= e[3] < hi
            and (e[4] >= MIN_NS or not e[0].startswith("/device:"))]
    lines = sorted({(e[0], e[1]) for e in events})
    Path(out).write_bytes(gzip.compress(
        json.dumps({"lines": lines, "events": keep}).encode()))
    print(f"kept {len(keep)} of {len(events)} events", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), sys.argv[3])
