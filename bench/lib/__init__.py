"""The benchmark's yardstick: traffic generation, the reference and the
comparison that decides ``correct``, the trace reduction, the roofline
work functions, and the code that drives each entry point."""
