"""fit.refits_per_refresh: synopses the program refitted because an insert
left them stale, per refresh the window inserted.

Read from the program's process-wide fit counter `aqp.synopsis.fits`
(`repro.data.aqp_store`, which declares it at import), its fits labelled
`stale=1`: the run's store is the process's only one, and nothing refits
before the window's first insert.  A program without that counter reads
None."""


def read(ctx):
    if ctx.refreshes <= 0:
        return None
    import repro.data.aqp_store  # noqa: F401  (declares the counter)
    from repro import obs

    reg = obs.get_registry()
    if not reg.collect_counters("aqp.synopsis.fits"):
        return None
    return reg.sum_counter("aqp.synopsis.fits", stale=1) / ctx.refreshes
