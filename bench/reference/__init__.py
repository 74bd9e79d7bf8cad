"""The benchmark's yardstick: input generators, the float64 direct sum,
the lower-precision control and the algorithm's work count. Nothing here
imports the program under test."""
