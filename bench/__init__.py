"""On-chip benchmark of the fault-tolerant train and serve paths."""
