"""The model kinds' modules (`bench/models/<kind>.py`): found by name,
making the same weights, payloads, reference outputs and FLOP counts
from a seed as before they were split out of the shared files, and the
only place that names a kind."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import flops, harness, reference, serving

SEED = 2 ** 31 + 3
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# At each kind's TINY size and SEED: (sum, sum of squares) in float64 of
# the weights, of a 6-payload pool and of the bfloat16-operand reference
# forward over its first 4 payloads; the useful FLOPs and roofline
# seconds of a 4-slot launch.  Checksums, not hashes of the bytes, so
# that a last-place difference in the CPU's transcendental functions
# does not fail them; a changed tag, constant or shape moves them far.
PINS = {
    "dcgan": {"params": (4.753226967830415, 444.20967119301264),
              "pool": (3.7052783891558647, 26.64272599890959),
              "forward": (-59.6670890582609, 33.90856585664943),
              "flops": 2916352.0, "roofline_s": 2.720976800976801e-07},
    "aspp": {"params": (3.4824268492520787, 22.30389032166324),
             "pool": (1570.600355315255, 1999.7753604714808),
             "forward": (-146.36843733489513, 231.40435101007057),
             "flops": 1197504.0, "roofline_s": 1.3087179487179487e-07},
}
SHARED = ["serving.py", "reference.py", "flops.py", "control.py",
          "drivers/serve_open.py", "drivers/serve_closed.py",
          "drivers/train.py"]


def checksum(tree):
    leaves = [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]
    return (sum(float(a.sum()) for a in leaves),
            sum(float(np.square(a).sum()) for a in leaves))


@pytest.mark.parametrize("kind", sorted(PINS))
def test_kind_makes_what_it_made_before(kind):
    mod = harness.load_model(kind)
    model, pin = mod.TINY, PINS[kind]
    params = mod.params(model, SEED)
    pool = np.asarray(mod.payload_pool(model, SEED, 6))
    num = reference.numerics({"numerics": {"matmul_operands": "bfloat16"}})
    out = serving.serve_forward(model, mod, num)(params, pool[:4])
    assert checksum(params) == pytest.approx(pin["params"], rel=1e-6)
    assert checksum(pool) == pytest.approx(pin["pool"], rel=1e-6)
    assert checksum(out) == pytest.approx(pin["forward"], rel=1e-6)
    ops = mod.ops(model, 4)
    assert flops.total_flops(ops) == pin["flops"]
    assert flops.roofline_s(ops, PEAKS) == pytest.approx(pin["roofline_s"],
                                                         rel=1e-12)


def test_training_state_batch_and_step_count_unchanged():
    mod = harness.load_model("dcgan")
    model = dict(mod.TINY, batch=8)
    assert checksum(mod.gan_params(model, SEED)) == pytest.approx(
        (4.776399130398204, 488.191004598219), rel=1e-6)
    assert checksum(mod.train_batch(model, SEED, 1)) == pytest.approx(
        (36.559333110344596, 9855.602000200497), rel=1e-6)
    assert flops.total_flops(mod.gan_step_ops(model, 8)) == 54714368.0


def test_a_kind_without_a_module_names_the_missing_file(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "MODELS", tmp_path)
    with pytest.raises(FileNotFoundError, match="nokind.py"):
        harness.load_model("nokind")


def test_shared_files_name_no_kind():
    kinds = [p.stem for p in harness.MODELS.glob("[!_]*.py")]
    for name in SHARED:
        text = (harness.BENCH / name).read_text()
        assert '["kind"] ==' not in text, name
        for kind in kinds:
            assert f'"{kind}"' not in text, (name, kind)
