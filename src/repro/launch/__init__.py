"""The training launcher: node mesh, its state shardings, checkpoint cadence."""
