"""Plain references of the benchmark: frozen copies of the program's
packer, architecture model, timing oracle and netlist evaluator.  Nothing
here imports the program."""
