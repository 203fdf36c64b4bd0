"""Where the benchmark meets the program: one module per entry-point family,
found by the name a configuration file gives under ``program``. An adapter
builds the program's model from the configuration's numbers, lays the
reference's seeded weights out as the program's parameter tree, and builds
the entry the window drives. Nothing else of the benchmark imports the
program."""
