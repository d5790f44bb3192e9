"""setup_s: from the start of the process to the first measured step —
reaching the chip, building the Program, startup (weights made on the
device from the seed), compiling or reading back the step program, and
the warm-up steps."""


def compute(run):
    return run["setup_s"]
