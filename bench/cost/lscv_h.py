"""LSCV_h bandwidth (paper eqs. 22-29, 39-43) for one axis of n points: one
`jit_lscv_h` program.

The O(n^2 n_h) stage visits the n (n - 1) / 2 pairs i < j once: per pair the
S value (difference 1, square 1, scale by 1 / sigma^2 1 = 3), and per pair
and grid point the exponent's scale 1, exp 1, the square e^2 1, two
multiply-adds c_kk e - 2 c_k e^2 (2 mul, 2 add) 4 and accumulation 1 = 8.
The O(n) covariance (3 n) and the grid's O(n_h) scalar steps are counted
too.  Bytes: the covariance and the grid stage each read the n float32
points.
"""
N_H = 150


def call_flops(n: int) -> float:
    pairs = n * (n - 1) / 2.0
    return pairs * (3 + 8 * N_H) + 3.0 * n + 10.0 * N_H


def call_bytes(n: int) -> float:
    return 2.0 * 4.0 * n
