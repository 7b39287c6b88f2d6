"""itl_ms_p95: 95th percentile (nearest rank) of the gaps between
consecutive tokens of a session, over every such gap in the window, in
milliseconds. A pause for a tool call is not a gap."""
import math


def p95(values):
    ordered = sorted(values)
    return ordered[max(0, math.ceil(0.95 * len(ordered)) - 1)]


def read(run):
    gaps = run.window.gaps_s()
    return 1e3 * p95(gaps) if gaps else None
