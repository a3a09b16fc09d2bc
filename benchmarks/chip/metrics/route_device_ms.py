"""Device time per step of the operations under the routers' scopes
(``route<i>``: the router, top-k, the sort, and the row gathers into the
expert buffer and back), forward and backward, per chip.

Routing holds no planned kernel, so it is no stage of the family's; the
trace reduction still files its operations under the scope that the
family's pattern finds, and this reads them there."""

import re

ROUTE = re.compile(r"route\d+")


def read(r):
    if r.trace is None:
        return None
    ms = r.trace.per_step_ms(lambda c: sum(
        ns for name, ns in c.bucket_ns.items() if ROUTE.fullmatch(name)))
    return ms if ms > 0 else None
