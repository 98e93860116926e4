"""GROUP BY family on a diagonal-bandwidth joint: the shared box terms once
per point, then one window on the group axis per category (the factored
kernel), plus the per-category interval moments on the full boxes.

Per sample point, for a family of `groups` categories: shared axes
(d - 1) x 21 and their product 2 (d - 2), once; per category the group
axis's Phi difference with its z's 11, the product with the shared terms 2
and accumulation 2.  Moments: one `box` moments pass per category.
Per answered category (one query part) this is the per-category work plus a
1 / groups share of the shared work.
"""
from bench.cost import box

SHARED_AXIS = 21
PER_GROUP = 11 + 2 + 2


def query_flops(n: int, d: int, groups: int) -> float:
    shared = SHARED_AXIS * (d - 1) + 2 * max(d - 2, 0)
    moments = box.AXIS * d + 2 * (d - 1) + 1 + 3 + 5
    return float(n) * (shared / max(groups, 1) + PER_GROUP + moments)


def call_bytes(n: int, d: int) -> float:
    return 2.0 * 4.0 * n * d
