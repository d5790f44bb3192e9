"""repeat.saved_gib (layer: Looped stack). GiB that the step's `repeat` op
keeps across its loop for the backward pass: the `pt_repeat_saved_bytes` gauge
of the program's metrics registry at the window's close (`run["registry"]`;
`ops/control_flow_ops.py:repeat_kernel` sets it from the traced shapes: every
turn's carries and the stacked turn outputs). With `remat` that is all the
forward loop hands to the backward loop besides the weights; one turn's
activations live inside the backward loop's body. Nothing to read where the
program registers no such gauge."""


def compute(run):
    saved = (run.get("registry") or {}).get("pt_repeat_saved_bytes")
    return None if saved is None else saved / 2.0**30
