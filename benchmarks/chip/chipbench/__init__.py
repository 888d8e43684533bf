"""The on-chip serving benchmark's own code: traffic generation, the run
record, metric arithmetic, the trace reduction, model FLOPs, the seeded
weights and the plain float32 reference.  Nothing here imports the
serving program except ``chipbench.cell``, which drives it."""
