"""setup_s: from the start of the process to the first measured step,
what a user waits for before training starts: reaching the chip, building the
Program, startup (weights made on the device from the seed, and a fingerprint
of each read back), compiling or reading back the step program, and the
warm-up steps. The plain reference is not in it: it runs after the window
(PR 30). The `info` line's `setup_split_s` gives the four parts."""


def compute(run):
    return run["setup_s"]
