"""The harness end to end on the CPU at a tiny size: files found by name,
a sound run comes out correct, and a run whose served path is broken, or
whose tokens come from the fp8 control, does not.

Every run here skips the harness's look for a chip and serves a tiny
Qwen-shaped model (d 128, 4 layers, vocabulary 4096) from files written into
a temporary checkout."""
import json
import math
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
# limits of the tiny cells, set on the CPU from six seeds of each: the
# program's widest gap read up to 0.028 and the control's from 0.076; the
# program's mean gap up to 3.3e-4 and the control's from 2.4e-4 (dense) and
# 6.1e-3 (paged, the cell whose control is tested)
TINY_LIMIT = 0.05
TINY_MEAN_LIMIT = 1.5e-3


def tiny_root(root: Path) -> Path:
    """A checkout holding BENCHMARK.json and bench/ with one tiny cell a
    traffic mix: ``tiny.tb`` (dense, controller on) and ``tinyp.td``
    (paged with the table-walk kernel, batch filled before the window)."""
    for d in ("metrics", "configs", "traffic"):
        shutil.copytree(BENCH / d, root / "bench" / d)
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    tiny = json.loads((BENCH / "configs/qwen1.5-0.5b.json").read_text())
    tiny.update(name="tiny", hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=4, vocab_size=4096)
    tiny["serve"].update(max_batch=4, max_seq=256, stages=2)
    tiny["correct"] = {"sample_tokens": 10**6, "max_logit_gap": TINY_LIMIT,
                       "mean_logit_gap": TINY_MEAN_LIMIT}
    tp = json.loads(json.dumps(tiny))
    tp.update(name="tinyp", tie_word_embeddings=False)
    tp["serve"].update(paged=True, paged_kernel=True, stages=1)
    for c in (tiny, tp):
        (root / f"bench/configs/{c['name']}.json").write_text(json.dumps(c))
    b = json.loads((BENCH / "traffic/bursty.json").read_text())
    b["arrivals"]["mean_rate"] = 6.0
    b["prompt"].update(median=40, min=8, max=120)
    b["output"].update(median=32, min=16, max=64)
    b["max_total"] = 255
    for p, n in zip(b["controller"]["profiles"], (2, 4)):
        p["stages"] = n
    d = json.loads((BENCH / "traffic/decode-heavy.json").read_text())
    d["arrivals"]["mean_rate"] = 3.0
    d["prompt"].update(median=20, min=8, max=60)
    d["output"].update(median=16, min=8, max=64)
    d["max_total"] = 255
    for name, t in (("tb", b), ("td", d)):
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(t))
    bm["configs"] = [
        dict(name=n, source="https://huggingface.co/Qwen/Qwen1.5-0.5B",
             file=f"bench/configs/{n}.json", reduced=[], why="tiny")
        for n in ("tiny", "tinyp")]
    cells = {"tiny.tb": "qwen1.5-0.5b.bursty",
             "tinyp.td": "qwen1.5-110b-pp20.decode-heavy"}
    bm["workloads"] = [dict(name=k, config=k.split(".")[0],
                            traffic=k.split(".")[1], chips=1, why="tiny")
                       for k in cells]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [k for k, v in cells.items()
                              if v in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bm, indent=1))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tiny_root(tmp_path_factory.mktemp("checkout"))
    yield r
    # the harness turned on the persistent compile cache inside the
    # temporary checkout: turn it off again for the tests that follow
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()


def test_benchmark_cells_resolve():
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end + cell.per_layer} \
            == set(cell.readers)


def test_new_files_are_found_by_name(root):
    """A configuration, a traffic mix and a metric added as files, with
    entries in BENCHMARK.json only, reach a cell with no other edit."""
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["name"] = "dummy-model"
    (root / "bench/configs/dummy-model.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/tb.json").read_text())
    tr["arrivals"]["mean_rate"] = 1.5
    (root / "bench/traffic/dummy-mix.json").write_text(json.dumps(tr))
    (root / "bench/metrics/dummy_count.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append(dict(name="dummy-model", source="x",
                              file="bench/configs/dummy-model.json",
                              reduced=[], why="a test"))
    bm["workloads"].append(dict(name="dummy-model.dummy-mix",
                                config="dummy-model", traffic="dummy-mix",
                                chips=1, why="a test"))
    bm["per_layer"].append(dict(
        name="dummy_count", unit="steps", better="higher",
        source="host_clock", layer="engine tick", moves="itl_p95_ms",
        workloads=["dummy-model.dummy-mix"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.load_cell("dummy-model.dummy-mix", root)
    assert cell.config["name"] == "dummy-model"
    assert cell.traffic["arrivals"]["mean_rate"] == 1.5
    assert "dummy_count" in cell.readers
    assert cell.readers["dummy_count"].read(
        harness.Run(cell, {}, {}, 1.0, 0.0, [], [1, 2], [], False)) == 2.0
    # and the cells that were there before are unchanged
    assert "dummy_count" not in harness.load_cell("tiny.tb", root).readers


def test_missing_file_is_an_error(root):
    with pytest.raises(FileNotFoundError):
        harness.find_file(root / "bench/traffic", "no-such-mix")


@pytest.mark.parametrize("cell", ["tiny.tb", "tinyp.td"])
def test_sound_run_is_correct(root, cell):
    out = harness.run_cell(cell, 2**31 + 11, 3.0, False, root=root,
                           check_chips=False)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"itl_p95_ms", "output_tokens_per_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0


def test_no_chip_means_no_run():
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


def _break_tick(monkeypatch, fault: str):
    """Plant ``fault`` in the fused decode tick, the timed path's program."""
    from repro.serving.executor_cache import FusedDecodeProgram
    step = FusedDecodeProgram.step
    n = [0]

    def broken(self, caches, tok, pos, block_tables=None):
        if fault == "state_unchanged":
            # the tick's cache writes are lost: the state it returns is the
            # state it was given
            kept = [jax.tree.map(jnp.copy, c) for c in caches]
            nxt, _ = step(self, caches, tok, pos, block_tables)
            return nxt, kept
        nxt, new = step(self, caches, tok, pos, block_tables)
        # one slot's token, in turn, altered where the tick produces it
        n[0] += 1
        i = n[0] % nxt.shape[0]
        return nxt.at[i].set((nxt[i] + 1) % 4096), new

    monkeypatch.setattr(FusedDecodeProgram, "step", broken)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
@pytest.mark.parametrize("cell", ["tiny.tb", "tinyp.td"])
def test_broken_path_is_not_correct(root, monkeypatch, cell, fault):
    _break_tick(monkeypatch, fault)
    out = harness.run_cell(cell, 2**31 + 11, 2.0, False, root=root,
                           check_chips=False)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_is_not_correct(root, seed):
    """The control, the reference with fp8 weights standing in for the
    served tokens, fails the harness's own comparison on the same served
    requests, which the program passes: each number the configuration
    holds, the widest gap and the mean gap, separates the two."""
    cell = harness.load_cell("tinyp.td", root)
    p = harness.prepare(cell, seed, root, check_chips=False)
    fill, arr = harness.make_traffic(p, cell.traffic, 3.0, seed)
    srv = harness.serve(p, arr, fill, 3.0, None, harness.Spans(False))
    while srv.busy():                  # let every request in flight finish
        srv.step()
    done = [r for r in srv.records.values() if not math.isnan(r.finished)]
    prog = harness.check_outputs(cell, p.params, done, seed)
    ctl = harness.check_outputs(cell, p.params, done, seed, control=True)
    assert harness.is_correct(prog), prog
    assert not harness.is_correct(ctl), ctl
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert prog[name]["value"] <= prog[name]["limit"] \
            < ctl[name]["value"]


def test_sample_holds_the_longest():
    recs = [harness.ReqRecord(i, 0.0, np.zeros(3, np.int64), n,
                              tokens=[0] * n) for i, n in
            enumerate([5, 50, 7, 9, 11])]
    s = harness.sample_requests(recs, 3, 60)
    assert s[0].rid == 1
    assert sum(len(r.tokens) for r in s) >= 60
    assert s == harness.sample_requests(recs, 3, 60)
