"""fit.collapsed_axis_share: the share of the process's one-axis LSCV_h
fits whose grid sums ran over the axis's distinct values with their
multiplicities, in percent.

Read from the program's process-wide counter `aqp.synopsis.axis_fits`
(`repro.data.aqp_store`, which declares it at import), its fits labelled
`collapsed=1` over all its `lscv_h` fits, warm-up fits included.  A program
without that counter reads None; one that has it and fitted no LSCV_h axis
collapsed none, and reads 0."""


def read(ctx):
    import repro.data.aqp_store  # noqa: F401  (declares the counter)
    from repro import obs

    reg = obs.get_registry()
    if not reg.collect_counters("aqp.synopsis.axis_fits"):
        return None
    fits = reg.sum_counter("aqp.synopsis.axis_fits", selector="lscv_h")
    if not fits:
        return 0.0
    return 100.0 * reg.sum_counter("aqp.synopsis.axis_fits",
                                   selector="lscv_h", collapsed=1) / fits
