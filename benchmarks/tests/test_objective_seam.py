"""A job's objective is found by name on both sides.

The seam: the traffic file's ``objective`` entry names
``objectives/<name>.py`` (the program's ``ClientLogic`` and metric) and
``reference/objectives/<name>.py`` (the targets both sides start from, and
the reference's loss). Two things are held here.

Nothing moved for the cells there are: ``class_label`` is the rule and the
loss the harness had hard-wired, to the bit (data for a seed of every real
cell's toy twin against the rule written out again here; the two modules
against the program's own pieces).

The room is real: everything a ``model_config`` PR would bring for a model
whose loss is no class label (a token denoiser trained by blocks, the files
of ``objective_rehearsal/``) comes in as NEW files and BENCHMARK.json entries,
runs ``correct`` through ``window.run_cell`` on the CPU, and comes out not
correct under the float8 control and under each of three faults planted in
the objective.
"""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmarks.harness import build, check, datagen, window
from benchmarks.harness.spec import BENCH_DIR, Cell, load_json, load_module

from . import toy

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "objective_rehearsal")
REAL = load_json(os.path.join(toy.REPO, "BENCHMARK.json"))
CELL = "toy_denoiser.toy_block_denoise"
OBJECTIVE = "toy_block_denoise"


def rehearsal_files() -> list[str]:
    """What the PR that follows adds, as paths under ``benchmarks/``."""
    out = []
    for sub, _, files in os.walk(REHEARSAL):
        out += [os.path.relpath(os.path.join(sub, f), REHEARSAL)
                for f in files if not f.endswith(".pyc")]
    return sorted(out)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """A toy root with the rehearsal's files laid over it: (root, cell)."""
    root, bm = toy.make_root(str(tmp_path_factory.mktemp("objective")))
    bench = os.path.join(root, "benchmarks")
    for rel in rehearsal_files():
        dst = os.path.join(bench, rel)
        assert not os.path.exists(dst), f"{rel} is no new file"
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copy(os.path.join(REHEARSAL, rel), dst)
    cfg = load_json(os.path.join(bench, "configs", "toy_denoiser.json"))
    bm["configs"].append({
        "name": "toy_denoiser", "source": cfg["source"], "reduced": [],
        "file": "benchmarks/configs/toy_denoiser.json", "why": "a token head"})
    bm["workloads"].append({
        "name": CELL, "config": "toy_denoiser", "traffic": OBJECTIVE,
        "chips": 1, "why": "a loss that is no class label"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root, Cell(CELL, root=root)


# -- nothing moved: class_label is the hard-wired rule, to the bit ---------

def test_a_traffic_file_that_names_no_objective_gets_the_default():
    default = load_json(os.path.join(BENCH_DIR, "defaults.json"))["objective"]
    for w in REAL["workloads"]:
        cell = Cell(w["name"], root=toy.REPO)
        assert "objective" not in cell.job
        assert cell.objective == {"name": default}
    for kind in ("objectives", "reference/objectives"):
        assert os.path.exists(os.path.join(BENCH_DIR, kind, default + ".py"))


@pytest.mark.parametrize("name", [w["name"] for w in REAL["workloads"]])
def test_the_twins_data_is_the_hard_wired_rule_to_the_bit(rehearsal, name):
    """Tokens, lengths and labels of a seed as the parent's ``make_data``
    drew them: its draw written out again, key for key."""
    import jax
    import jax.numpy as jnp

    root, _ = rehearsal
    twin = "toy_{config}.toy_{traffic}".format(
        **next(w for w in REAL["workloads"] if w["name"] == name))
    cell = Cell(twin, root=root)
    seed = 2**31 + 34
    _, _, got, rows = build.make_inputs(cell, seed)
    inp = load_module("reference", cell.family, cell.bench_dir).input_spec(
        cell.cfg, cell.job)
    n_max, n = max(rows), max(rows) + int(cell.job["val_examples"])
    seq, shape = int(inp["seq"]), (int(cell.job["clients"]), n, int(inp["seq"]))
    key = jax.random.fold_in(jax.random.PRNGKey(datagen.seed31(seed)), 7)
    k_x, _, k_len, _ = jax.random.split(key, 4)
    tok = jax.random.randint(k_x, shape, 1, int(inp["vocab"]), jnp.int32)
    lo = max(1, int(seq * float(inp.get("min_len_frac", 1.0))))
    length = jax.random.randint(k_len, shape[:2] + (1,), lo, seq + 1)
    x = np.asarray(jnp.where(jnp.arange(seq)[None, None, :] < length, tok, 0))
    y = ((x[..., 0] + x[..., 1]) % int(inp["classes"])).astype(np.int32)
    want = (x[:, :n_max], y[:, :n_max], x[:, n_max:], y[:, n_max:])
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape and (g == w).all()


def test_class_label_is_the_programs_own_loss_and_metric():
    from fl4health_tpu.clients import engine
    from fl4health_tpu.metrics import efficient

    side = load_module("objectives", "class_label")
    model_def = object()
    logic = side.build_logic(model_def, {}, {})
    assert type(logic) is engine.ClientLogic and logic.model is model_def
    assert logic.criterion is engine.masked_cross_entropy
    manager = side.build_metrics({}, {})
    assert [m.name for m in manager.metrics] == [efficient.accuracy().name]
    assert manager.prefix == ""


def test_class_label_is_the_references_own_cross_entropy():
    """The loss that was ``reference/strategies/fedavg.cross_entropy``: the
    same jaxpr as the moved lines written out again, and no strategy file
    names a loss any more."""
    import jax
    import jax.numpy as jnp

    plain = load_module("reference/objectives", "class_label")

    def moved(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    logits = jnp.linspace(-2.0, 3.0, 24).reshape(6, 4)
    labels = jnp.arange(6) % 4
    assert str(jax.make_jaxpr(lambda a, b: plain.loss(a, None, b, None))(
        logits, labels)) == str(jax.make_jaxpr(moved)(logits, labels))
    for name in ("fedavg", "fedavg_adapters"):
        with open(os.path.join(BENCH_DIR, "reference", "strategies",
                               name + ".py")) as f:
            text = f.read()
        assert "cross_entropy" not in text and "softmax" not in text
        assert not hasattr(load_module("reference/strategies", name),
                           "cross_entropy")


def test_harness_code_names_no_objective_and_build_names_no_loss():
    names = {f[:-3] for kind in ("objectives", "reference/objectives")
             for f in os.listdir(os.path.join(BENCH_DIR, kind))
             if f.endswith(".py")}
    assert "class_label" in names
    for f in os.listdir(os.path.join(BENCH_DIR, "harness")):
        if f.endswith(".py"):
            with open(os.path.join(BENCH_DIR, "harness", f)) as fh:
                text = fh.read()
            assert not [n for n in names if n in text], f
            if f == "build.py":
                assert "masked_cross_entropy" not in text
                assert "accuracy" not in text


# -- the room is real: a token denoiser from new files alone ---------------

def test_the_rehearsal_brings_new_files_only(rehearsal):
    root, cell = rehearsal
    bench = os.path.join(root, "benchmarks")
    for sub, _, files in os.walk(BENCH_DIR):
        rel = os.path.relpath(sub, BENCH_DIR)
        if rel.split(os.sep)[0] in ("tests", "fixtures", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".pyc"):
                continue
            with open(os.path.join(sub, f), "rb") as a, \
                    open(os.path.join(bench, rel, f), "rb") as b:
                assert a.read() == b.read(), f"{rel}/{f} was edited"
    assert rehearsal_files() == [
        "configs/toy_denoiser.json",
        "families/toy_denoiser.py",
        "flops/toy_denoiser.py",
        "limits/toy_denoiser.toy_block_denoise.json",
        "objectives/toy_block_denoise.py",
        "reference/objectives/toy_block_denoise.py",
        "reference/toy_denoiser.py",
        "traffic/toy_block_denoise.json",
    ]
    assert cell.objective["name"] == OBJECTIVE and cell.objective["mask_id"] == 63


def test_the_targets_are_the_objectives_own_and_drawn_once(rehearsal):
    _, cell = rehearsal
    o = cell.objective
    _, _, (x, y, xv, yv), rows = build.make_inputs(cell, 2**31 + 8)
    x, y = np.asarray(x), np.asarray(y)
    n_clients, n, seq = int(cell.job["clients"]), max(rows), 16
    assert x.shape == (n_clients, n, 2, seq) and x.dtype == np.int32
    assert y.shape == (n_clients, n, seq) and y.dtype == np.float32
    assert np.asarray(xv).shape[1:] == (4, 2, seq) and np.asarray(yv).shape[1:] == (4, seq)
    noised, clean = x[:, :, 0], x[:, :, 1]
    masked = y > 0
    assert (noised[masked] == o["mask_id"]).all() and (clean[masked] > 0).all()
    assert (noised[~masked] == clean[~masked]).all() and clean.max() < o["mask_id"]
    # one level a block: the weights of a block's masked positions agree
    blocks = (n_clients, n, -1, o["block_length"])
    t, at = 1.0 / np.where(masked, y, 1.0).reshape(blocks), masked.reshape(blocks)
    assert t[at].min() >= o["t_min"] and t[at].max() <= o["t_max"]
    spread = np.where(at, t, -1.0).max(-1) - np.where(at, t, 2.0).min(-1)
    assert spread[at.any(-1)].max() < 1e-6
    assert 0.3 < masked.sum() / (clean > 0).sum() < 0.9
    again = build.make_inputs(cell, 2**31 + 8)[2]
    assert (np.asarray(again[0]) == x).all() and (np.asarray(again[1]) == y).all()


def test_the_sound_toy_is_correct_with_no_compile_in_the_window(rehearsal):
    _, cell = rehearsal
    res = window.run_cell(cell, 2**31 + 34, 0.5, False, toy.fake_device(1),
                          time.perf_counter())
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert set(line["checks"]) >= {"loss_r1_gap", "loss_r2_gap", "loss_r3_gap",
                                   "grad1_gap", "dparam_gap"}
    assert set(line["metrics"]) == {m["name"]
                                    for m in cell.metrics("end_to_end")}


def test_the_float8_control_is_not_correct(rehearsal):
    _, cell = rehearsal
    limits = cell.limits()
    for seed in (2**31 + 5, 2**31 + 6, 2**31 + 7):
        sim, prog = window.first_rounds(cell, seed)
        window.release(sim)
        ref = window.reference_rounds(cell, seed)
        sound = check.numbers(prog, ref)
        assert check.decide(sound, limits)[0], (seed, sound)
        low = window.reference_rounds(cell, seed, numerics="float8_operands")
        bad = check.numbers(low, ref)
        ok, checks = check.decide(bad, limits)
        assert not ok, (seed, bad)
        failing = [k for k, c in checks.items() if c["value"] > c["limit"]]
        assert any(bad[k] > 3 * sound[k] for k in failing), (sound, bad)


def _the_program_ignores_the_weights(sim, cell, monkeypatch):
    """1 on every masked position where the loss has 1/t."""
    real = sim.logic.training_loss
    sim.logic.training_loss = lambda preds, features, batch, *a: real(
        preds, features, batch.replace(y=(batch.y > 0).astype(batch.y.dtype)),
        *a)


def _the_program_reads_the_clean_row(sim, cell, monkeypatch):
    """The model is shown the answer: row 1 where it should read row 0."""
    real = sim.logic.predict
    sim.logic.predict = lambda params, model_state, batch, *a, **kw: real(
        params, model_state, batch.replace(x=batch.x[:, ::-1]), *a, **kw)


def _the_reference_draws_targets_of_its_own(sim, cell, monkeypatch):
    """The program's data is made; the reference's, made after the window,
    gets another targets' key: other levels and other masked positions."""
    import jax

    plain = load_module("reference/objectives", OBJECTIVE, cell.bench_dir)
    real = plain.targets
    monkeypatch.setattr(plain, "targets", lambda key, *a: real(
        jax.random.fold_in(key, 1), *a))


@pytest.mark.parametrize("fault", [_the_program_ignores_the_weights,
                                   _the_program_reads_the_clean_row,
                                   _the_reference_draws_targets_of_its_own])
def test_a_fault_planted_in_the_objective_is_not_correct(rehearsal,
                                                         monkeypatch, fault):
    _, cell = rehearsal
    res = window.run_cell(
        cell, 2**31 + 34, 0.3, False, toy.fake_device(1), time.perf_counter(),
        break_program=lambda sim: fault(sim, cell, monkeypatch))
    assert res["failed"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["correct"] is False, res["checks"]
