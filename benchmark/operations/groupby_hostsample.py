"""Operation `groupby_hostsample`: `groupby`'s query stream, check and
readings unchanged, with a warm-up sweep over a sample of the hosts.

`groupby.sweep_queries` sends every window for every host in turn,
because below the scan-cache budget a server that has been up for a
while holds each host's window of each segment.  At a scale where a
point query's plan is over that budget the route keeps nothing per
host, and the full sweep (7 x 1000 sequential queries at scale 1000)
would only lengthen set-up.  Here the same windows go out for every
`hosts // warmup.sweep_hosts`-th host: every segment is touched, in the
one- and the two-segment shape, and the warm loop that follows ends, as
for `groupby`, when the compile ledger has stood still for a pass.
"""

from __future__ import annotations

from benchmark.operations import groupby
from benchmark.operations.groupby import (READINGS, check,  # noqa: F401
                                          combine, make_queries)


def sweep_queries(traffic: dict, data) -> list[dict]:
    if traffic["hosts"] != 1:
        raise ValueError("groupby_hostsample: hosts is 1")
    n = min(data.hosts, int(traffic["warmup"]["sweep_hosts"]))
    step = data.hosts // n
    sampled = set(range(0, step * n, step))
    return [q for q in groupby.sweep_queries(traffic, data)
            if q["hosts"][0] in sampled]
