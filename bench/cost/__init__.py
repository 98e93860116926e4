"""Operation and byte counts of the algorithms the layers run, from shapes.

One module per computation, found by the `cost` name in a layer file:

    query_flops(n, d, groups) -> operations to answer one query (estimate
                                 and interval passes) over n points in d axes
    call_bytes(n, d)          -> bytes one flush must read (the sample, once
                                 per pass)

or, for a fit, `call_flops(n)` / `call_bytes(n)` per program call.  An
operation is one arithmetic op or one transcendental (erf, exp), whatever
implements it: the count is the algorithm's, not an implementation's.
"""
