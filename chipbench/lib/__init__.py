"""The chip benchmark's yardstick: traffic, weights, the serving-loop
adapter, the trace reduction, the roofline arithmetic and the check
that decides `correct`. Nothing here is imported by the program."""
