"""The benchmark's own arithmetic: spec loading, device checks and peaks,
conv work counts, trace reduction and the comparison that decides
``correct``.  Nothing here imports the program under test."""
