"""The one place the repo names jax/orbax APIs that have moved before.

``shard_map`` is ``jax.shard_map`` (``check_vma``; ``axis_names`` = the set
of MANUAL mesh axes, the rest left to GSPMD). The kernels
(ops/attention.py, ops/ring.py, ops/head_ce.py, ops/loss.py) and the
pipeline schedule import it from here.
"""

from __future__ import annotations

import os as _os

from jax import shard_map  # noqa: F401  (re-exported)

# Async checkpoint writes (utils/checkpoint.py AsyncSaver) prefer orbax's
# AsyncCheckpointer for the background shard write when the installed orbax
# exposes it; otherwise the writer thread falls back to the synchronous
# StandardCheckpointer. Either way the train loop only pays the host
# snapshot — this gate selects the writer implementation, not the overlap.
# TPU_TRAINER_NO_ORBAX_ASYNC=1 forces the fallback (used by tests to cover
# both writers on one orbax version).
try:
    import orbax.checkpoint as _ocp

    ORBAX_ASYNC_OK = (
        hasattr(_ocp, "AsyncCheckpointer")
        and hasattr(_ocp, "StandardCheckpointHandler")
        and hasattr(_ocp.args, "StandardSave")
        and not _os.environ.get("TPU_TRAINER_NO_ORBAX_ASYNC")
    )
except ImportError:  # orbax absent entirely (inference-only installs)
    ORBAX_ASYNC_OK = False
