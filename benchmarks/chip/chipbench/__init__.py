"""The chip benchmark's own library, shared by every architecture: traffic,
weights, reference, trace reduction and roofline arithmetic.  What one
architecture's layer is (its sizes, leaves, plain layer and counts) lives in
its module under ``archs/``.  Only ``harness`` imports the program under
test (to drive it and to read its parameter layout)."""
