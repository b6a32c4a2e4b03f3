"""The jitted train step of each benchmark cell, cut to two layers, as a
jaxpr: is a refactor's compiled step still the parent's?

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python scripts/step_jaxpr.py OUT_DIR

Run it from the root of each of two trees and `diff -r` the directories.
It traces (nothing is lowered, compiled or run), at the cells' own widths,
batch and job, with the kernel dispatches steered to their TPU branch, so
the Pallas calls of `ops/flash.py`, `ops/head_ce.py` and
`ops/grouped_matmul.py` are in the text. A jaxpr's text holds no source
location and no object address, so the two texts compare as they are.
"""

import json
import pathlib
import re
import sys
from unittest import mock

import jax

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# Two layers of every kind a cell has: for LFM2 the leading dense conv
# layer and an attention layer with experts; for the latent-attention family
# the leading dense layer, a sparse one and the prediction module.
CUT = {"num_hidden_layers": 2}
CUT_FAMILY = {"lfm2_moe": {**CUT, "num_dense_layers": 1,
                           "layer_types": ["conv", "full_attention"]},
              "mla_moe": CUT}


class _Chip:
    platform = "tpu"


def step_jaxpr(cell: str) -> str:
    import importlib

    from perf import program
    from perf.runners import train_family

    def load(kind, name):
        return json.loads((ROOT / "perf" / kind / f"{name}.json").read_text())

    work = load("workloads", cell)
    cfg = load("configs", work["config"])
    traffic = load("traffic", work["traffic"])
    job = work["job"]
    devices = jax.devices()[:work["chips"]]
    if "family" in cfg:
        family = importlib.import_module(f"perf.families.{cfg['family']}")
        trainer = train_family.build_trainer(
            family, {**cfg, **CUT_FAMILY[cfg["family"]]}, traffic, job,
            devices)
    else:
        trainer = program.build_trainer({**cfg, **CUT}, traffic, job, devices)
    state = jax.eval_shape(trainer._make_state, jax.random.PRNGKey(0))
    rows = traffic["tokens_per_step"] // traffic["seq_len"]
    accum = job["grad_accum"]
    batch = jax.ShapeDtypeStruct(
        (accum, rows // accum, traffic["seq_len"]), "int32")
    with mock.patch.object(jax, "devices", lambda *a: [_Chip()]), \
            mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(trainer._step_jit.trace(state, batch).jaxpr)
    # A frozenset of strings prints in the order of this process's hashes.
    return re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})"
                  % ", ".join(sorted(m.group(1).split(", "))), text)


if __name__ == "__main__":
    out = pathlib.Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    for path in sorted((ROOT / "perf" / "workloads").glob("*.json")):
        text = step_jaxpr(path.stem)
        (out / f"{path.stem}.jaxpr.txt").write_text(text)
        print(path.stem, len(text.splitlines()), "lines,",
              text.count("pallas_call"), "pallas_call")
