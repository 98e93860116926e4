"""Axis-aligned box on a diagonal-bandwidth joint (paper eq. 11), estimate
and interval moments.

Per (query, sample point): on each of d axes the 1-D terms of `range1d`
(z's 4, Phi difference 7, phi difference 7, first moment 3 = 21); the COUNT
and SUM products over axes 2 (d - 1); target select 1; accumulation 2 in the
estimate pass and, in the moments pass, squares and cross term 3 and five
accumulations 5.
Bytes: each pass reads the n x d float32 points once.
"""
AXIS = 21


def query_flops(n: int, d: int, groups: int = 1) -> float:
    per_pass = AXIS * d + 2 * (d - 1) + 1
    return float(n) * ((per_pass + 2) + (per_pass + 3 + 5))


def call_bytes(n: int, d: int) -> float:
    return 2.0 * 4.0 * n * d
