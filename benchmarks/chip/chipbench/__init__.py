"""The chip benchmark's yardstick: traffic generation, weights from the
seed, the client that drives the scheduler, the plain reference, trace
reduction and the arithmetic of FLOPs, bytes and peaks. The program
under test is imported only by `cli.build_program`."""
