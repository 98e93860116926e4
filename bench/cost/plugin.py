"""PLUGIN bandwidth (paper eqs. 12-19) for one axis of n points.

The two O(n^2) stages sum a derivative kernel over the n (n - 1) / 2 pairs
i < j: difference 1, scale 1, square 1, the polynomial of K6 (3 mul, 3 add)
or K4 (2 mul, 2 add), exp 1 with its argument 1, product 1, accumulation 1:
13 for Psi6, 11 for Psi4.  The O(n) variance (3 n) and the scalar steps are
counted too.  Bytes: each stage reads the n float32 points.
"""


def call_flops(n: int) -> float:
    pairs = n * (n - 1) / 2.0
    return pairs * (13 + 11) + 3.0 * n


def call_bytes(n: int) -> float:
    return 3.0 * 4.0 * n
