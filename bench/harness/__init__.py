"""The benchmark's yardstick: everything that turns a run of the program
into numbers lives here, apart from the program, so that a change to
the program cannot change how it is measured."""
