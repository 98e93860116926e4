"""The reference's estimators, one module per bandwidth selector, found by the
configuration's `engine.selector` (`estimators/<selector>.py`).  A selector
with no module here is refused before any data is made; it is never checked
against another selector's reference.

Each module defines:

    program_bandwidth(syn)      -> the program's bandwidth of a fitted synopsis,
                                   as float64 (reads only its attributes)
    bandwidth(x, prec)          -> the reference's own bandwidth of a sample x
                                   ((m,) or (m, d) float32), computed in `prec`
                                   (`reference.Precision`)
    bandwidth_gap(prog, ref)    -> the number compared as `h_gap`
    answers(boxes, aggs, x, bw, n_seen, prec)
                                -> (estimate, 95% half-width, reference count,
                                   target scale M) per box, from `x` at `bw`
                                   (boxes as `reference.box_of` makes them)

The module owns everything that depends on the selector: the bandwidth and
the kernel the answers integrate.  Reservoir replay, boxes, exact counts and
precision stay in `reference.py`.
"""
