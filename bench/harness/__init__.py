"""The harness shared by every cell: device, traces, spans, rooflines."""
