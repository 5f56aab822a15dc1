"""How full the ragged kernel's query rows are: over the traced slice's
``dispatch`` spans, the live tokens (the span's ``tokens``) times the
query heads, over the query rows the kernel's programs hold in one
layer (the span's ``attn_q_rows``: KV heads x query blocks x block
tokens x query heads per KV head, dead padding included), in %. A tick
with free slots, or a block rounded up past its last token, holds rows
that do no work; the ragged kernel's roofline share alone cannot tell
those rows from a slow KV walk.

The scheduler's ``TickTracer`` puts ``attn_q_rows`` on the span where
the step runs the Pallas ragged kernel; the profiler keeps span
arguments as event stats, which the harness's trace reduction does not
carry, so this reader opens the profile that ``bench/run.py`` wrote to
its temporary trace directory and takes the one whose ``bench.window``
span is the traced slice's. It reads nothing where no traced tick ran
the ragged kernel on the device, or no span carries ``attn_q_rows``."""
import glob
import os
import tempfile

from bench.driver import WINDOW_SPAN

LAYER = "kernels"
UNIT = "%"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
SPAN = "dispatch"
TRACE_DIRS = "bench-trace-*"        # bench/run.py's TemporaryDirectory prefix


def _host_events(pd):
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from line.events


def fill(pd, lo: float, hi: float, heads: int):
    """The fill (%) of the dispatch spans that start in [lo, hi) of a
    ``jax.profiler.ProfileData``; None where none carries ``attn_q_rows``."""
    live = rows = 0
    for e in _host_events(pd):
        if e.name != SPAN or not lo <= e.start_ns < hi:
            continue
        args = dict(e.stats)
        if "attn_q_rows" in args:
            live += int(args["tokens"]) * heads
            rows += int(args["attn_q_rows"])
    return 100.0 * live / rows if rows else None


def _profile_of(r):
    """The profile whose traced slice is ``r``'s, newest first."""
    from jax.profiler import ProfileData
    pattern = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        pd = ProfileData.from_file(path)
        if any(e.name == WINDOW_SPAN and float(e.start_ns) == r.lo
               for e in _host_events(pd)):
            return pd
    return None


def read(r):
    if not any(t.kernel_ns > 0 for t in r.ticks):
        return None
    pd = _profile_of(r)
    return None if pd is None else fill(pd, r.lo, r.hi, r.dims.heads)
