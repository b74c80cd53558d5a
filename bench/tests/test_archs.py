"""Architecture files: every serving configuration finds its own by
``model_type``, with the interface the serving driver and readers use,
and a model of a new architecture runs with new files only."""

import hashlib
import inspect
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import counts
import devtrace
import harness
from conftest import SERVE_CONFIGS, SPEC, small_serve_config
from drivers import serve

ROOT = harness.ROOT
DATA = pathlib.Path(__file__).parent / "data"


def _leaves(tree):
    return [(jax.tree_util.keystr(p), tuple(x.shape), np.dtype(x.dtype))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.mark.parametrize("config", SERVE_CONFIGS)
def test_arch_and_reference_found_by_model_type(config):
    c = harness.load_config(SPEC, config)
    arch = harness.arch(c["model_type"])
    for name in ("program_config", "init_leaf", "prefill_flops",
                 "decode_flops", "page_bytes", "small"):
        assert callable(getattr(arch, name)), name
    assert arch.DECODE_KERNELS and all(
        isinstance(k, str) and k for k in arch.DECODE_KERNELS)
    assert arch.prefill_flops(c, 256) > arch.decode_flops(c, 256) > 0
    assert arch.page_bytes(c, c["service"]["page_size"]) > 0
    ref = harness.reference(c["model_type"])
    params = inspect.signature(ref.served_gaps).parameters
    assert {"control", "pad_p"} <= set(params)
    assert params["control"].kind is inspect.Parameter.KEYWORD_ONLY


@pytest.mark.parametrize("config", SERVE_CONFIGS)
def test_weights_are_the_program_tree(config):
    """At CPU-test widths the benchmark's weights have the structure,
    shapes and dtypes of the program's own ``init_params``."""
    from repro.models import lm
    c = small_serve_config(config)
    cfg = harness.arch(c["model_type"]).program_config(c)
    program = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                             jax.random.PRNGKey(0))
    weights = serve.make_weights(c, 3)
    assert (jax.tree_util.tree_structure(weights)
            == jax.tree_util.tree_structure(program))
    assert _leaves(weights) == _leaves(program)


# sha256 over every leaf (its path, dtype, shape, then its bytes) of the
# qwen3-1.7b weights at CPU-test widths from seed 2**31 + 7, taken when the
# serving driver still wrote Qwen3's tree by hand: the chat cell's readings
# hold only while its weights are drawn as they were then
QWEN3_SMALL_SHA256 = (
    "67234bfde44b62420c948fab6dffeaefdc4a3e26ef8c7dc52d9c8069096d41e6")


def test_qwen3_weights_bit_for_bit():
    c = small_serve_config("qwen3-1.7b")
    w = serve.make_weights(c, harness.seed_words(2**31 + 7)(0))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    assert h.hexdigest() == QWEN3_SMALL_SHA256


def test_qwen3_counts_and_kernel_are_the_dense_ones():
    """Qwen3's operations, bytes and decode kernel are ``counts.py``'s
    dense ones and the Mosaic custom call, so the chat cell's per-layer
    readings are those the cell had before it took them from its
    architecture file; the decode roofline's reader gives that reading
    on a stretch recorded on the chip."""
    c = harness.load_config(SPEC, "qwen3-1.7b")
    arch = harness.arch("qwen3")
    assert arch.prefill_flops(c, 300) == counts.prefill_flops(c, 300)
    assert arch.decode_flops(c, 300) == counts.decode_flops(c, 300)
    assert arch.page_bytes(c, 8) * 1234 == counts.paged_decode_bytes(
        c, 8, 1234)
    mosaic = 'custom_call_target="tpu_custom_call"'
    assert arch.DECODE_KERNELS == (mosaic,)

    tr = json.loads((DATA / "trace_chat.json").read_text())
    peaks = harness.peaks("TPU v5 lite")
    window = serve.Window(1.0, {}, {}, {}, [], 0, 0, [], True, trace=tr,
                          traced={"pages0": 100, "pages1": 1334})
    reader = harness.load_module(
        harness.BENCH / "metrics" / "paged_decode_roofline.py",
        "metric_paged_decode_roofline")
    got = reader.read({"config": c, "window": window, "trace": tr,
                       "peaks": peaks})
    want = harness.bytes_share(counts.paged_decode_bytes(c, 8, 1234),
                               devtrace.time_matching(tr, [mosaic]) / 1e9,
                               peaks["hbm_bytes_per_s"])
    assert got == want and got > 0


def _digests(d):
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts
            and p.relative_to(d).parts[0] != "out"}


_RUN_COPY = """
import json, sys
import conftest
out = {}
for trace in (0, 1):
    result, checks = conftest.run_cell(
        "qwen3-copy.chat", conftest.small_serve_config("qwen3-copy"),
        conftest.small_chat_mix(), trace=trace)
    out[trace] = {"correct": result["correct"], "checks": checks,
                  "metrics": sorted(result["metrics"])}
out["files"] = [sys.modules[m].__file__
                for m in ("arch_qwen3_copy", "reference_qwen3_copy")]
print(json.dumps(out))
"""


def test_new_architecture_needs_files_only(tmp_path):
    """A copy of the benchmark takes a configuration of a new
    ``model_type`` (its arch and reference files copies of Qwen3's under
    the new name) with new files and new ``BENCHMARK.json`` entries
    only, and a small run of its cell comes out correct."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = _digests(bench)

    for part in ("archs", "reference"):
        shutil.copy(bench / part / "qwen3.py", bench / part / "qwen3_copy.py")
    c = json.loads((bench / "configs" / "qwen3-1.7b.json").read_text())
    c.update(name="qwen3-copy", model_type="qwen3_copy")
    (bench / "configs" / "qwen3-copy.json").write_text(json.dumps(c))
    spec = json.loads(harness.SPEC_FILE.read_text())
    spec["configs"].append(dict(
        next(e for e in spec["configs"] if e["name"] == "qwen3-1.7b"),
        name="qwen3-copy", file="bench/configs/qwen3-copy.json"))
    spec["workloads"].append(dict(
        next(w for w in spec["workloads"] if w["config"] == "qwen3-1.7b"),
        name="qwen3-copy.chat", config="qwen3-copy"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "qwen3-1.7b.chat" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["qwen3-copy.chat"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", _RUN_COPY],
                       cwd=bench / "tests", env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["files"] == [str(bench / "archs" / "qwen3_copy.py"),
                            str(bench / "reference" / "qwen3_copy.py")]
    for trace in ("0", "1"):
        assert out[trace]["correct"], out[trace]["checks"]
    assert {"ttft_p50_ms", "itl_p95_ms", "setup_s"} <= set(
        out["0"]["metrics"])
    assert {"decode.mfu", "prefill.mfu"} <= set(out["1"]["metrics"])

    after = _digests(bench)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "archs/qwen3_copy.py", "configs/qwen3-copy.json",
        "reference/qwen3_copy.py"]
