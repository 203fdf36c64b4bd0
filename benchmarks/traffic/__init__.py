"""Traffic: everything a window feeds the program, made from the seed and a
cell's data file by generators that know no cell by name."""
