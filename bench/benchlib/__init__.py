"""The benchmark's yardstick: cell loading, traffic generation, the plain
references, the correctness comparison, trace reduction, FLOP and byte
counts and the peak table. Nothing here is imported by the program."""
