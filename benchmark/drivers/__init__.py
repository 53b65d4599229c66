"""Traffic drivers. A mix file under ``benchmark/traffic`` names one of
these modules (``"driver"``); each has ``run(ctx) -> dict`` and drives the
system under test through the program's own entry point."""
