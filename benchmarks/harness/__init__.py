"""The benchmark's yardstick: loaders, traffic, drivers, trace reduction,
counts, peaks and the plain reference. Nothing here imports the program
except the two drivers, which call its public entry points."""
