from .aqp_store import (CategoricalSketch, CountMinSketch, MultiReservoir,
                        Reservoir, SynopsisCache, TelemetryStore,
                        TieredReservoir)
