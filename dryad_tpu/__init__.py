"""dryad_tpu — a TPU-native data-parallel dataflow framework.

A brand-new implementation of the capabilities of Microsoft Research's
Dryad + DryadLINQ (declarative partitioned queries -> optimized DAG ->
fault-tolerant distributed execution), designed for TPUs: query stages trace
to jax.jit/shard_map programs over a device mesh; hash/range/group shuffles
are XLA collectives over ICI; a host-side DAG scheduler provides replay-based
fault tolerance.  See SURVEY.md for the reference analysis.
"""

__version__ = "0.2.0"

from dryad_tpu.api.dataset import Context, Dataset  # noqa: F401,E402
from dryad_tpu.parallel.mesh import make_mesh  # noqa: F401,E402
from dryad_tpu.plan.expr import Decomposable  # noqa: F401,E402
