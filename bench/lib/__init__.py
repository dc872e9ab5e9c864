"""The benchmark's shared code: harness, what runners share, trace
reduction and the plain references."""
