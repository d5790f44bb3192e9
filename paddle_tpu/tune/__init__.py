"""paddle_tpu.tune: where a kernel's tiles are decided, and the sweep
that checks the decision on the chip.

One rule: a kernel family's tile, block or fused-or-scan choice is a
function of its shapes and dtype, written ONCE (space.py); the only
thing that can overrule it is a programmatic `overrides.forcing(...)`,
which tests and a sweep use. No table, no file, no environment
variable, no flag.

  space.py     per family: the legality predicate (Mosaic tile rules +
               the VMEM-budget models), the candidates a sweep walks,
               the default rule, and `pick(family, params, dtype)`, the
               one function the kernels call at trace time.
  overrides.py force / forcing / forced_config, and forced_key(), which
               the Executor folds into its jit cache key so that a
               change of a forced config re-traces.
  harness.py   the sweep: time every legal candidate under `forcing`,
               cross-check its numbers against the reference lowering,
               return the ranking. Refuses to time off a TPU; writes
               nothing.

CLI: `python -m paddle_tpu tune --kernel bahdanau --shape B=256,S=60,\
A=512,C=512 [--dry-run]` prints the ranking (see cli.py). What a sweep
finds becomes the family's rule in space.py.
"""

from . import overrides  # noqa: F401
from . import space  # noqa: F401
from . import harness  # noqa: F401
from .harness import TuningUnavailable, make_oracle, tune_case  # noqa: F401
from .overrides import force, forcing  # noqa: F401
from .space import pick  # noqa: F401
