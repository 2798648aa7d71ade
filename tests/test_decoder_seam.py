"""The seam between a decoder family and the decode drivers, held by the
imports themselves and by the one form of loader.

A family module (`pipeedge_tpu/models/<family>.py`) sees its stage's cache
through `models/stage_cache.py` and shares code through `models/decoder.py`;
it reaches neither up into `parallel/decode.py` nor sideways into another
family's private names. Nothing here compiles a program.
"""
import ast
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from pipeedge_tpu.models import ShardConfig, registry

MODELS = pathlib.Path(registry.__file__).parent
REPO = MODELS.parent.parent

# tensor parallelism's, not the cache's: the one import of `parallel.decode`
# a family keeps (ROADMAP D1 names it a debt)
ALLOWED_UP = {("llama.py", "tp_vocab_head_finalize")}


def _imports(tree):
    """(module as written, level, names) of every import in `tree`,
    whatever function it hides in."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            yield node.module or "", node.level, [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, 0, []


def test_no_family_reaches_up_into_the_driver_or_sideways_into_a_family():
    trees = {path.name: ast.parse(path.read_text())
             for path in sorted(MODELS.glob("*.py"))}
    families = {name[:-3] for name, tree in trees.items() if any(
        isinstance(node, ast.Assign) and any(
            getattr(target, "id", None) == "FAMILY" for target in node.targets)
        for node in tree.body)}
    assert {"gpt2", "llama", "keye", "kimi", "qwen3_next", "lfm2",
            "laguna", "minicpm_sala", "nemotron_h"} <= families
    up, sideways = [], []
    for name, tree in trees.items():
        for module, level, names in _imports(tree):
            parts = module.split(".")
            if parts[-2:] == ["parallel", "decode"] or (
                    parts[-1] == "parallel" and "decode" in names):
                up += [(name, n) for n in names or [module]
                       if (name, n) not in ALLOWED_UP]
            if level == 1 and parts[0] in families - {name[:-3]}:
                sideways += [(name, module, n) for n in names
                             if n.startswith("_")]
    assert not up, f"models/ imports parallel.decode: {up}"
    assert not sideways, f"a family's private names, imported: {sideways}"


# the published configurations of the seven families: the benchmark's key
# scheme writes a file from each, cut to the tiny registry entry its overlay
# under `tests/benchmark_checks/tiny/configs/` names (`program_model`; kimi's
# and qwen3-next's hold a share of the experts and half the vocabulary)
CONFIGS = ("keye-vl-2.0-30b-a3b", "kimi-k2-instruct",
           "qwen3-next-80b-a3b-instruct", "lfm2-8b-a1b", "laguna-xs.2",
           "minicpm-sala", "nemotron-3-super-120b-a12b")


@pytest.mark.parametrize("name", CONFIGS)
def test_init_params_and_load_params_give_one_tree(name, tmp_path):
    """A file in the published key scheme, written by the benchmark's writer
    (`benchmark/schemes/<model_type>.py`, which knows nothing of a family's
    `_assemble`), loads into `init_params`' tree: structure, shapes and
    dtypes, leaf by leaf, float32 where the family keeps a leaf so."""
    config = {}
    for folder in (REPO / "benchmark" / "configs",
                   REPO / "tests" / "benchmark_checks" / "tiny" / "configs"):
        config.update(json.loads((folder / f"{name}.json").read_text()))
    path = weights.write(config, 7, str(tmp_path / "weights.npz"))
    entry = registry.get_model_entry(config["program_model"])
    assert entry.family.FAMILY.decoder_model
    family, cfg = entry.family, entry.config
    stage = ShardConfig(1, entry.layers, is_first=True, is_last=True)
    with np.load(path) as tensors:
        loaded = family.load_params(cfg, stage, tensors, dtype=jnp.bfloat16)
    drawn = family.init_params(cfg, stage, dtype=jnp.bfloat16)
    described = jax.tree_util.tree_map(
        lambda leaf: (leaf.shape, leaf.dtype), (loaded, drawn))
    assert described[0] == described[1]
    assert jax.tree_util.tree_structure(loaded) \
        == jax.tree_util.tree_structure(drawn)
    kept = {leaf.dtype for leaf in jax.tree_util.tree_leaves(drawn)}
    assert kept <= {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}
