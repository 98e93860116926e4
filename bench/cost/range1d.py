"""1-D range closed forms (paper eqs. 9-10) and their interval moments.

Per (query, sample point):
  estimate pass: z_a, z_b (2 sub, 2 mul) 4; Phi difference (2 erf, 2 mul,
  2 add, 1 sub) 7; phi difference (2 exp, 4 mul, 1 sub) 7; SUM term
  x dPhi - h dphi 3; two accumulations 2                           = 23
  moments pass: the same 21 terms, then c^2, s^2, c s 3 and five
  accumulations 5                                                  = 29
Bytes: each pass reads the n float32 points once.
"""
ESTIMATE = 23
MOMENTS = 29


def query_flops(n: int, d: int = 1, groups: int = 1) -> float:
    return float(n) * (ESTIMATE + MOMENTS)


def call_bytes(n: int, d: int = 1) -> float:
    return 2.0 * 4.0 * n
