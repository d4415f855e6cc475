"""The on-chip benchmark's harness: cell specs read from data files,
traffic, the measured and traced windows, the trace reduction and the
comparison with the plain references in ``chipref``."""
