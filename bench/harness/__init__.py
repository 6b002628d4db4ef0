"""The benchmark's general machinery: cells, traffic, client, trace
reduction, roofline arithmetic and the correctness comparison."""
