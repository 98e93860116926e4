"""Entry points: `serve` runs the AQP admission loop over a TelemetryStore."""
