"""step.unnamed_ms (layer: Executor step). Device time per step that still
carries no program op's name: the leaf rows of the trace's op table
(`run["trace"]["ops"]`) with an empty scope that the program's record of its
compiled step program (`pt_executor_instruction_scope` in `run["registry"]`;
`step.xla_inserted_ms.py` has the join) does not name either. The guard of
that reader: the two sum to what the op table has without a scope, and this
one should stay near 0 (a constant shared by several ops, another program's
rows in the window). Its `info` lists every such row over 0.05 ms a step.
Nothing to read without a trace or where the program publishes no such
family."""

from chipbench.readers import load_reader

NAMED = "step.xla_inserted_ms"
LISTED_FROM_MS = 0.05


def compute(run):
    found = load_reader(NAMED).split(run)
    if found is None:
        return None
    return sum(r["ns"] for r in found[1]) / 1e6 / run["steps"]


def info(run):
    rows = [[r["name"], r["opcode"], r["shape"], r["ns"] / 1e6 / run["steps"]]
            for r in load_reader(NAMED).split(run)[1]]
    return {"rows": sorted((r for r in rows if r[3] > LISTED_FROM_MS),
                           key=lambda r: -r[3])}
