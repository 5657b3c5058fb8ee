"""The architecture module: what a configuration's ``"reference"`` names
holds all that is particular to one architecture.  Its Qwen2 weights are
bit for bit those the benchmark drew before the layout moved into the
module, and a published key that the module neither maps nor lists stops
the build instead of being dropped."""

import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import model, spec, weights

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = sorted((ROOT / "perfbench/configs").glob("*.json"))
QWEN2 = json.loads((ROOT / "perfbench/configs/qwen2_0_5b-ideal.json").read_text())
TINY = dict(
    QWEN2["sizes"], hidden_size=64, intermediate_size=128, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
)
# sha256 over (key path, shape, dtype, bytes) of every leaf, keys sorted, of
# the tiny-size weights of seed 2**31 + 9, made on the CPU by the draws as
# they stood before the layout moved into the architecture module.
PUBLISHED_DIGEST = "a906bd9ec4c0d4899a8a5ad40bd2f5ecb0c872d4bf1558dca2a1a2317e78e8f8"
PROGRAM_DIGEST = "033396564106259234d643aee69a929a0df29224aaf88c412de1af35b5246276"


def digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in sorted(leaves, key=lambda pl: jax.tree_util.keystr(pl[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_qwen2_weights_are_bit_identical_in_both_layouts():
    arch = spec.architecture(QWEN2)
    seed = 2**31 + 9
    assert digest(weights.published_weights(arch, TINY, seed)) == PUBLISHED_DIGEST
    assert digest(weights.program_weights(arch, TINY, seed)) == PROGRAM_DIGEST


def test_the_loader_gives_one_module_per_path():
    arch = spec.architecture(QWEN2)
    assert spec.architecture(dict(QWEN2)) is arch
    assert Path(arch.__file__) == ROOT / QWEN2["reference"]
    assert spec.find_cell("qwen2_0_5b-ideal.batch").arch is arch


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_published_key_of_a_configuration_is_accounted_for(path):
    config = json.loads(path.read_text())
    fields = model.program_fields(spec.architecture(config), config["sizes"])
    assert fields["num_layers"] == config["sizes"]["num_hidden_layers"]


def test_an_unmapped_published_key_stops_the_build():
    config = json.loads(json.dumps(QWEN2))
    config["sizes"]["n_routed_experts"] = 64
    with pytest.raises(ValueError, match="n_routed_experts"):
        model.build(config, 1, spec.architecture(config))


def test_no_other_benchmark_file_names_an_architecture_module():
    modules = {Path(json.loads(p.read_text())["reference"]).stem for p in CONFIGS}
    for p in (ROOT / "perfbench").rglob("*.py"):
        if p.stem not in modules:
            text = p.read_text()
            assert not [m for m in modules if m in text], p
