"""BENCHMARK.json, the cells' files, and the benchmark's copies of the
program's generators, checked without a chip."""
import dataclasses
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import harness
import workcount

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"] and 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/") and len(c["why"]) <= 200
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200 and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"] and "\n" not in m["layer"]
        for w in m["workloads"]:
            assert harness._applies(e2e[m["moves"]], w)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(workload):
    cell = harness.load_cell(workload, BENCH)
    assert cell.driver().run and cell.reference()
    assert set(cell.limits) and all(v > 0 for v in cell.limits.values())
    for m in cell.per_layer:
        reader = harness.load_module(os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py"))
        assert callable(reader.read)
    assert any(m["name"] != "setup_s" for m in cell.end_to_end) and cell.per_layer


def test_unknown_device_has_no_peaks():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), "--workload",
         "paper-nu-10k.spmv", "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=harness.ROOT)
    assert out.returncode != 0 and "{" not in out.stdout


@pytest.mark.parametrize("spec", [
    dict(rows=300, cols=200, row_splits=6, col_splits=5, num_blocks=12, uniform=False),
    dict(rows=300, cols=300, row_splits=6, col_splits=6, num_blocks=40, uniform=True),
])
def test_vbr_structure_is_the_papers_generator(spec):
    from repro.core.vbr import synthesize

    ref = harness.load_module(os.path.join(harness.BENCH_DIR, "references", "vbr_ref.py"))
    st = ref.make_structure(dict(spec, structure_seed=7))
    v = synthesize(spec["rows"], spec["cols"], spec["row_splits"], spec["col_splits"],
                   spec["num_blocks"], uniform=spec["uniform"], seed=7)
    for k in ("rpntr", "cpntr", "bindx", "bpntrb", "bpntre", "indx"):
        assert np.array_equal(getattr(st, k), getattr(v, k)), k
    dense = v.to_dense().astype(np.float64)
    x = np.random.default_rng(0).standard_normal(spec["cols"])
    y, scale = ref.Reference(st, v.val).product(x)
    assert np.allclose(y, dense @ x) and np.allclose(scale, np.abs(dense) @ np.abs(x))


def test_paper_matrix_size():
    cell = harness.load_cell("paper-nu-10k.spmv", BENCH)
    st = cell.reference().make_structure(cell.config)
    assert st.shape == (10_000, 10_000) and st.num_blocks == 500
    work = workcount.staged_call(st, None)
    assert work["bytes"] == 4 * (st.stored_nnz + 20_000)
    assert 15e6 < st.stored_nnz < 25e6


def test_granite_patterns_and_config_are_the_programs():
    from repro.models.layers import sable_patterns

    cell = harness.load_cell("granite-8b-sable.batch", BENCH)
    ref = cell.reference()
    mcfg = ref.program_config(cell.config)
    assert (mcfg.d_model, mcfg.n_layers, mcfg.d_ff, mcfg.vocab_size) == (4096, 36, 14336, 49152)
    mine = ref.patterns(cell.config)
    for name, p in sable_patterns(mcfg).items():
        assert np.array_equal(p.rows, mine[name][0]) and np.array_equal(p.cols, mine[name][1])
    per_token, per_key, per_row = ref.work(cell.config)
    # two operations per weight of the layers: attention (41.9M) and a
    # quarter of the three FFN matrices (44.0M) in each of 36 layers
    assert per_token == 2 * 36 * (41_943_040 + 3 * 896 * 128 * 128)
    assert per_key == 4 * 36 * 32 * 128 and per_row == 2 * 4096 * 49152


def _serving_cells() -> dict:
    """One cell of every configuration that the serving driver runs."""
    cells = {}
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"], BENCH)
        if cell.traffic["driver"] == "closed_loop":
            cells.setdefault(cell.config_name, cell)
    return cells


SERVING = _serving_cells()


@pytest.mark.parametrize("config", sorted(SERVING))
def test_serving_reference_keeps_the_contract(config):
    cell = SERVING[config]
    ref = cell.reference()
    for fn in ("program_config", "make_params", "scores", "work"):
        assert callable(getattr(ref, fn, None)), fn
    mcfg = ref.program_config(cell.config)
    cfg = cell.config
    assert (mcfg.n_layers, mcfg.d_model, mcfg.vocab_size) == (
        cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"])
    counts = ref.work(cfg)
    assert len(counts) == 3 and all(int(n) == n > 0 for n in counts)


@pytest.mark.parametrize("change", [{"hidden_size": 2048}, {"num_key_value_heads": 32}],
                         ids=["width", "kv_heads"])
def test_program_config_raises_on_a_size_departure(change):
    cell = harness.load_cell("granite-8b-sable.batch", BENCH)
    with pytest.raises(ValueError):
        cell.reference().program_config(dict(cell.config, **change))


def test_program_config_raises_on_a_moved_tile_pattern(monkeypatch):
    import repro.models.layers as layers

    real = layers.sable_patterns

    def moved(mcfg):
        pats = real(mcfg)
        p = pats["out"]
        pats["out"] = dataclasses.replace(
            p, cols=tuple((c + 1) % (p.d_out // p.tk) for c in p.cols))
        return pats
    monkeypatch.setattr(layers, "sable_patterns", moved)
    cell = harness.load_cell("granite-8b-sable.batch", BENCH)
    with pytest.raises(ValueError, match="out pattern"):
        cell.reference().program_config(cell.config)
