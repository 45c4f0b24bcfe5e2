"""The architecture seam: a configuration names its architecture module
(``bench/architectures/<name>.py``), which gives its weights, its map onto
the program's ``ModelConfig`` and the shapes the roofline readers count.

``data/seam_parent.json`` holds what the harness gave the two dense
configurations before the seam, made with the parent commit's code on
the same inputs: weight digests, ``ModelConfig`` fields, and the kernel
counts and per-layer readings of recorded traces.  The tests hold the
seam to them.  A toy sparse-expert architecture (``data/toy_arch/``)
enters by new files alone, and a model key that no map covers is
refused.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import dataclasses
import gzip
import hashlib
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness as H  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402
from bench.weights import make_weights  # noqa: E402

DATA = Path(__file__).parent / "data"
PARENT = json.loads((DATA / "seam_parent.json").read_text())
CONFIGS = sorted(PARENT["weights"])
KERNELS = ("flash_decode_paged", "int8_matmul", "smooth_quant")
READERS = ("int8_matmul_roofline", "flash_decode_paged_roofline", "mfu")


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _leaves(tree):
    import jax
    return [(jax.tree_util.keystr(p), a)
            for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]]


# -- the dense configurations, through the seam, as before it ---------------

@pytest.mark.parametrize("name", CONFIGS)
def test_weight_layout_at_full_size_as_parent(name):
    """Keys, shapes and dtypes of the whole tree at the file's sizes."""
    import jax
    config = _config(name)
    build = H.architecture(config).make_weights
    tree = jax.eval_shape(lambda: build(config["model"],
                                        jax.random.PRNGKey(0)))
    rows = [[k, list(a.shape), str(a.dtype)] for k, a in _leaves(tree)]
    assert {"leaves": len(rows), "sha256": hashlib.sha256(
        json.dumps(rows).encode()).hexdigest()} == \
        PARENT["weights"][name]["full"]


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_values_at_cut_size_as_parent(name):
    """Every leaf's bytes, at a cut depth (and, for the 7B widths, cut
    widths) that the CPU holds, from a seed over 32 bits."""
    import numpy as np
    config = _config(name)
    config["model"].update(PARENT["cut"][name])
    got = {k: [list(a.shape), str(a.dtype),
               hashlib.sha256(np.asarray(a).tobytes()).hexdigest()]
           for k, a in _leaves(make_weights(config, PARENT["seed"]))}
    assert got == PARENT["weights"][name]["cut"]


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_as_parent(name):
    cfg = H.program_config(_config(name))
    assert {f.name: repr(getattr(cfg, f.name))
            for f in dataclasses.fields(cfg)} == \
        PARENT["program_config"][name]


@pytest.mark.parametrize("key", ["kv_lora_rank", "n_routed_experts"])
def test_unmapped_model_key_is_refused(key):
    config = _config("smollm-135m")
    config["model"][key] = 512
    with pytest.raises(ValueError, match=key):
        H.program_config(config)


# -- kernel counts and readers on recorded traces, as before the seam --------

def _reduced(case):
    path = DATA / case["events"]
    with (gzip.open(path, "rt") if path.suffix == ".gz" else
          path.open()) as f:
        d = json.load(f)

    def evs(rows):
        return [TR.Ev(n, a, b, st) for n, a, b, st in rows]
    mods = evs(d["modules"])
    if case["as_step"]:
        # an admission's programs, read as if the decode step ran them
        mods = [dataclasses.replace(m, name=TR.STEP_MODULE) for m in mods]
    return TR.reduce_events(evs(d["ops"]), mods, evs(d["host"]), d["lo"],
                            d["hi"], set(d["span_names"]))


def _ctx(cell, trace, c):
    """What a reader reads, from recorded numbers."""
    return SimpleNamespace(
        cell=cell, trace=trace, device_kind=c["device_kind"],
        traffic=SimpleNamespace(slots=c["slots"]),
        span=SimpleNamespace(t0=c["span"][0], t1=c["span"][1]),
        accept=tuple(c["accept"]), traced_steps=lambda: c["steps"],
        tokens_between=lambda a, b: c["tokens"],
        prefilled_between=lambda a, b: c["prompts"])


CASES = PARENT["readings"]


@pytest.mark.parametrize("case", CASES, ids=[c["id"] for c in CASES])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_counts_as_parent(case, kernel):
    r = _reduced(case)
    assert [r.kernel_calls(kernel), r.kernel_s(kernel)] == \
        case["kernels"][kernel]


@pytest.mark.parametrize("case", CASES, ids=[c["id"] for c in CASES])
@pytest.mark.parametrize("metric", READERS)
def test_reader_as_parent(case, metric):
    ctx = _ctx(H.load_cell(case["cell"]), _reduced(case), case["ctx"])
    assert H.reader(metric)(ctx) == case["readers"][metric]


# -- a second architecture, from new files alone ------------------------------

TOY = DATA / "toy_arch"
TOY_MODEL = json.loads((TOY / "configs" / "toy-moe.json").read_text())[
    "model"]


def _toy_benchmark(root: Path) -> None:
    """The benchmark's files with the toy's added: its configuration,
    architecture module and kernel reader; a mix and limits of a dense
    cell's kind; a ``BENCHMARK.json`` of one toy cell."""
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil
                    .ignore_patterns("tests", "__pycache__"))
    for f in TOY.rglob("*.*"):
        dest = root / "bench" / f.relative_to(TOY)
        assert not dest.exists(), f"{dest} is not a new file"
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(f, dest)
    shutil.copy(root / "bench" / "traffic" / "chat_closed32.json",
                root / "bench" / "traffic" / "toy_closed.json")
    shutil.copy(root / "bench" / "limits" / "smollm-chat-closed.json",
                root / "bench" / "limits" / "toy-moe-decode.json")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy-moe", "source": "test",
                     "file": "bench/configs/toy-moe.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "toy-moe-decode", "config": "toy-moe",
                       "traffic": "toy_closed", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "tokens_per_s.toy", "unit": "tokens/s",
                        "better": "higher", "bound": 0.1,
                        "source": "host_clock"}],
        "per_layer": [
            {"name": f"{m}.toy", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "kernels",
             "moves": "tokens_per_s.toy"}
            for m in ("expert_gemm_roofline", "int8_matmul_roofline",
                      "flash_decode_paged_roofline", "mfu")]}))


@pytest.fixture
def toy_cell(tmp_path, monkeypatch):
    _toy_benchmark(tmp_path)
    monkeypatch.setattr(H, "BENCH", tmp_path / "bench")
    return H.load_cell("toy-moe-decode", root=tmp_path)


def test_toy_architecture_gets_weights_and_program_config(toy_cell):
    config = toy_cell.config
    w = make_weights(config, 2**40 + 3)
    assert w["layers"][1]["moe"]["experts"]["down"]["w"].shape == (4, 32, 64)
    assert w["layers"][0]["attn"]["k"]["w"].shape == (64, 32)
    assert "ffn" not in w["layers"][0]
    cfg = H.program_config(config)
    assert (cfg.arch_type, cfg.num_layers, cfg.d_model, cfg.num_experts,
            cfg.experts_per_token, cfg.moe_d_ff, cfg.sliding_window) == \
        ("moe", 2, 64, 4, 2, 32, None)


def test_toy_architecture_counts(toy_cell):
    arch, m = H.architecture(toy_cell.config), TOY_MODEL
    assert arch.int8_matmul_calls(m, 16) == 2 * [
        (16, 64, 64), (16, 64, 32), (16, 64, 32), (16, 64, 64)] + [
        (16, 64, 512)]
    assert arch.expert_gemm_calls(m, 16) == 2 * [
        (4, 32, 64, 32), (4, 32, 64, 32), (4, 32, 32, 64)]
    per_layer = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 4 + 3 * 2 * 64 * 32
    assert arch.flops_per_token(m) == 2 * (2 * per_layer + 64 * 512)
    assert arch.attention_flops_per_position(m) == 4 * 2 * 4 * 16


def _kernel_ev(name, a, b):
    return TR.Ev(f"%{name}.3 = bf16[16,64]{{1,0}} custom-call(s8[16,64]"
                 f"{{1,0}} %x.1, s8[64,64]{{1,0}} %w.2)", a, b,
                 {"hlo_module": TR.STEP_MODULE})


def test_new_kernel_is_counted_and_read(toy_cell):
    """A kernel the toy adds is counted under its own name, beside the
    dense kernels, and the readers of both read the toy cell."""
    ops = [_kernel_ev("expert_gemm", 0.001 * i, 0.001 * i + 4e-4)
           for i in range(6)]
    ops += [_kernel_ev("int8_matmul", 0.01 + 0.001 * i,
                       0.01 + 0.001 * i + 2e-4) for i in range(9)]
    ops += [_kernel_ev("flash_decode_paged_int8", 0.02, 0.0201),
            _kernel_ev("flash_decode_paged_int8", 0.021, 0.0211),
            TR.Ev("%add.1 = bf16[16,64]{1,0} add(bf16[16,64]{1,0} "
                  "%custom-call.2, bf16[16,64]{1,0} %x.3)", 0.03, 0.031,
                  {"hlo_module": TR.STEP_MODULE})]
    mods = [TR.Ev("jit_counted(1)", 0.0, 0.04, {})]
    host = [TR.Ev(TR.TRACED_SPAN, 0.0, 0.05, {})]
    r = TR.reduce_events(ops, mods, host, 0.0, 0.05)
    assert r.kernel_calls("expert_gemm") == 6
    assert r.kernel_s("expert_gemm") == pytest.approx(6 * 4e-4)
    assert r.kernel_calls("int8_matmul") == 9
    assert r.kernel_calls("flash_decode_paged") == 2
    assert set(r.kernels) == {"expert_gemm", "int8_matmul",
                              "flash_decode_paged_int8"}
    assert dict(r.ops)["jit_counted/add"] == pytest.approx(0.001)
    ctx = _ctx(toy_cell, r, {"device_kind": "TPU v5 lite", "slots": 4,
                             "span": [0.0, 0.05], "accept": [40, 32],
                             "steps": [[40, 60, 80, 100]], "tokens": 40,
                             "prompts": [24]})
    got = {m["name"]: H.reader(m["name"])(ctx) for m in toy_cell.per_layer}
    assert set(got) == {"expert_gemm_roofline.toy", "int8_matmul_roofline.toy",
                        "flash_decode_paged_roofline.toy", "mfu.toy"}
    assert all(v is not None and 0.0 < v < 100.0 for v in got.values()), got
