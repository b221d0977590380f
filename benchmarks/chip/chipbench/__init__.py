"""The chip benchmark's own library: traffic, weights, reference, trace
reduction and roofline arithmetic.  It imports the program under test only
in ``harness`` (to drive it) and in ``weights`` (to read its parameter
layout)."""
