"""Data generators, one module per deployment kind, found by the `generator`
key of a configuration file.  Each module defines

    generate(rng, rows, params) -> {column: float32 array}

and the refresh batches of a cell come from the same function."""
