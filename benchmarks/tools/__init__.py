"""Tools of the benchmark's builder: calibration of the limits and the
recording of the small trace. No run of a cell uses them."""
