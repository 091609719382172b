"""One module per way of driving a cell: `run(ctx) -> harness.Outcome`."""
