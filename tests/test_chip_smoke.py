"""chip_smoke.py's phases, run on the CPU at yi-6b's reduced widths.

The script itself refuses to run anywhere but a TPU; its phase functions
are platform-independent, so the serving, executor-accounting and
reference-comparison logic it relies on is checked here.
"""
import importlib.util
import json
import pathlib

import pytest

from repro.core import hw
from util import check, run_with_devices

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
CS = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(CS)

SERVE = dict(slots=2, max_seq=64, buckets=(16, 64), n_requests=4, max_new=4,
             seed=0, on_tpu=False)


@pytest.fixture(scope="module")
def model():
    cfg = CS.get_config(CS.ARCH).reduced()
    params, rec = CS.build_params(cfg, seed=0)
    return cfg, params, rec


def test_params_phase_builds_the_whole_tree(model):
    cfg, params, rec = model
    assert rec["phase"] == "params" and rec["n_layers"] == cfg.n_layers
    assert rec["bytes"] == sum(x.nbytes for x in CS.jax.tree.leaves(params))


@pytest.mark.parametrize("mode", CS.MODES)
def test_served_logits_match_reference(model, mode):
    cfg, params, _ = model
    eng, rec = CS.serve_mode(cfg, params, mode, **SERVE)
    assert rec["requests"] == SERVE["n_requests"]
    assert rec["tokens_served"] == SERVE["n_requests"] * SERVE["max_new"]
    assert rec["rebinds"] == {"ftl_requalified_total": 0,
                              "ftl_xla_fallback_total": 0}
    # what ran is what the plan report resolves
    assert rec["ran"]["prefill"] == {
        k: [v] for k, v in rec["executors"]["prefill"].items()}

    got = CS.check_logits(eng, cfg, mode, prompt_len=30, n_new=5, seed=0)
    assert got["max_rel_err"] <= CS.LOGIT_RTOL
    assert got["token_agreement"] == 1.0
    # the tolerance is tight enough to catch a one-position RoPE slip in
    # the decoded tokens
    assert got["late_position_rel_err"] > CS.LOGIT_RTOL


def test_block_phase_runs_the_planned_mode(model):
    cfg, params, _ = model
    eng, _ = CS.serve_mode(cfg, params, CS.PLANNED_MODE, **SERVE)
    rec = CS.block_phase(eng)
    assert rec["finite"] and rec["m"] == SERVE["max_seq"]
    assert set(rec["executors"]) == {"gemm", "attention", "mlp"}


def test_train_parity_on_four_devices():
    """The --chips 4 phase at reduced width on four host devices."""
    check(run_with_devices(f"""
import importlib.util, json, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {str(_PATH)!r})
CS = importlib.util.module_from_spec(spec)
spec.loader.exec_module(CS)
rec = CS.train_parity(CS.get_config(CS.ARCH).reduced(), devices=jax.devices(),
                      n_layers=CS.TRAIN_LAYERS, batch=8, seq=32, steps=3,
                      seed=0, on_tpu=False)
assert rec["max_rel_err"] <= CS.LOSS_RTOL, rec
assert len(rec["losses_sharded"]) == 3
# the planned executors ran, the same on the mesh as on one device
assert rec["ran_sharded"] and rec["ran_sharded"] == rec["ran_one_chip"], rec
assert not any(rec["rebinds"].values()), rec
print(json.dumps(rec))
""", n_devices=4))


def test_rel_err_is_per_row():
    a = CS.np.array([[3.0, 4.0], [1.0, 0.0]])
    b = CS.np.array([[3.0, 4.0], [2.0, 0.0]])
    assert CS.rel_err(a, b).tolist() == [0.0, 0.5]


def test_main_refuses_the_cpu(capsys):
    assert CS.main([]) != 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_detect_target_raises_on_unknown_tpu_kind():
    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        hw.detect_target([Dev()])
