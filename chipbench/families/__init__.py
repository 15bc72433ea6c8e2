"""One module per model family: the parameter layout the program is held
to, the weight rules, the reference's layers and the FLOP and byte
counters of one engine call.  A configuration file names its family."""
