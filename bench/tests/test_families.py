"""Architecture families: the readings that must not move, and a family
that enters by new files alone.

The weights and the reference's gaps of each pinned configuration are
compared bit for bit with digests taken before its code moved into
``bench/families/``. XLA's CPU backend rounds the same program otherwise
in a process that may use only one CPU; these digests are of two or more.

A toy family, dense GQA with per-head RMS norms on q and k before RoPE
(the program's ``qk_norm``), is planted with its configuration, traffic,
cell and ``BENCHMARK.json`` in a temporary tree, and runs through the
whole harness with no file of ``bench/`` edited. Its reference runs each
request whole, prefix and tail together, not as ``gqa_dense`` does.
"""

import hashlib
import json
import pathlib
import sys

import jax
import numpy as np
import pytest

from bench import families, generator, reference, run, weights
from bench.tests import tiny

PINNED = json.loads((pathlib.Path(__file__).parent / "data"
                     / "pinned_readings.json").read_text())["readings"]
FILES = {c["name"]: c["file"] for c in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["configs"]}


def _tiny(config: str) -> dict:
    sizes = families.load(run.ROOT / FILES[config])
    return families.of(sizes).tiny(sizes)


@pytest.mark.parametrize("config", sorted(PINNED))
def test_weights_hold_their_pinned_digest(config):
    params = weights.make(_tiny(config), tiny.SEED)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PINNED[config]["weights"]


@pytest.mark.parametrize("config", sorted(PINNED))
def test_reference_gaps_hold_their_pinned_digest(config):
    """A prefix past one query chunk, three tails; with the control, and
    with no prefix."""
    sizes = _tiny(config)
    params = weights.make(sizes, tiny.SEED)
    rng = np.random.default_rng(17)
    V = sizes["vocab"]
    prefix = rng.integers(0, V, 1100).astype(np.int32)
    tails = [rng.integers(0, V, n).astype(np.int32) for n in (5, 17, 40)]
    served = [rng.integers(0, V, n).astype(np.int32) for n in (6, 9, 3)]
    found, control = reference.gaps(sizes, params, prefix, tails, served,
                                    128, control=True)
    bare = reference.gaps(sizes, params, np.zeros((0,), np.int32), tails,
                          served, 128)
    h = hashlib.sha256()
    for g in found + control + bare:
        h.update(np.asarray(g, np.float64).tobytes())
    assert h.hexdigest() == PINNED[config]["gaps"]


TOY = '''"""Dense GQA with a per-head RMS norm on q and on k before RoPE."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import families
from bench.families import gqa_dense
from bench.families.gqa_dense import (KEYS, finish_layer, kernel_call,
                                      matmul_params, tiny, token_flops)
from bench.reference import chunked_attend, f32, mm, rms, rope
from bench.weights import NORM_SPREAD

NORMS = {norms}


def program_fields(sizes):
    return dict(gqa_dense.program_fields(sizes), qk_norm=True)


def leaf_specs(sizes):
    shape = (sizes["n_layers"], sizes["head_dim"])
    return gqa_dense.leaf_specs(sizes) + [
        (("layers", "attn", "q_norm"), shape, "norm", NORM_SPREAD),
        (("layers", "attn", "k_norm"), shape, "norm", NORM_SPREAD)]


def project(a, x, pos, sizes, low):
    q, k, v = (mm("sd,dhk->shk", x, a[w], low) for w in ("wq", "wk", "wv"))
    if sizes["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    if NORMS:
        q = rms(q, a["q_norm"], sizes["norm_eps"])
        k = rms(k, a["k_norm"], sizes["norm_eps"])
    kind, theta = sizes["rope"], float(sizes["rope_theta"])
    return rope(q, pos, kind, theta), rope(k, pos, kind, theta), v


@functools.partial(jax.jit, static_argnums=(0, 1))
def layer(frozen, low, lp, x):
    sizes = families.thaw(frozen)
    pos = jnp.arange(x.shape[1])

    def one(xs):
        h = rms(xs, lp["norm1"], sizes["norm_eps"])
        q, k, v = project(lp["attn"], h, pos, sizes, low)
        att = chunked_attend(q, k, v, pos, pos, low)
        return finish_layer(lp, xs, att, sizes, low)

    return jax.lax.map(one, x)


def forward(sizes, params, prefix, inputs, low):
    frozen = families.freeze(sizes)
    rows = np.concatenate(
        [np.broadcast_to(prefix, (len(inputs), len(prefix))), inputs], 1)
    x = params["embedding"][jnp.asarray(rows)].astype(jnp.float32)
    for i in range(sizes["n_layers"]):
        x = layer(frozen, low, f32(jax.tree.map(lambda a: a[i],
                                                params["layers"])), x)
    return x[:, len(prefix):]
'''
PLANTED = {"qknorm_toy": True, "qknorm_toy_blind": False}


@pytest.fixture
def planted(tmp_path, monkeypatch):
    """A benchmark tree of its own: per family, a configuration (yi-6b-l8's
    sizes), a copy of the chat traffic and a cell, all named in its
    ``BENCHMARK.json``."""
    bench = tmp_path / "bench"
    for sub in ("configs", "families", "traffic", "workloads"):
        (bench / sub).mkdir(parents=True)
    real = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    base = next(w for w in real["workloads"] if w["name"] == "yi6b-chat-short")
    sizes = json.loads((run.ROOT / FILES[base["config"]]).read_text())
    cell = json.loads((run.BENCH / "workloads" / f"{base['name']}.json")
                      .read_text())
    (bench / "traffic" / "toy_chat.json").write_text(json.dumps(
        generator.load(base["traffic"])))
    listed = dict(real, configs=[], workloads=[], per_layer=[])
    for family, norms in PLANTED.items():
        (bench / "families" / f"{family}.py").write_text(
            TOY.replace("{norms}", str(norms)))
        (bench / "configs" / f"{family}-cfg.json").write_text(json.dumps(
            dict(sizes, family=family)))
        (bench / "workloads" / f"{family}-chat.json").write_text(json.dumps(
            dict(cell, config=f"{family}-cfg", traffic="toy_chat")))
        listed["configs"].append(dict(
            name=f"{family}-cfg", source="toy", reduced=[], why="toy",
            file=f"bench/configs/{family}-cfg.json"))
        listed["workloads"].append(dict(
            name=f"{family}-chat", config=f"{family}-cfg",
            traffic="toy_chat", chips=1, why="toy"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(listed))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "BENCH", bench)
    monkeypatch.setattr(generator, "TRAFFIC_DIR", bench / "traffic")
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(bench / "families")])
    yield
    for family in PLANTED:
        sys.modules.pop(f"{families.__name__}.{family}", None)


def test_planted_family_runs_and_its_control_fails(planted):
    spec = tiny.spec("qknorm_toy-chat")
    assert run.program_config(spec.sizes).qk_norm
    assert weights.make(spec.sizes, 0)["layers"]["attn"]["q_norm"].shape \
        == (spec.sizes["n_layers"], spec.sizes["head_dim"])
    result = tiny.run_tiny("qknorm_toy-chat", control=True)
    assert result["correct"], result["check"]
    assert result["info"]["checked_tokens"] > 20
    assert not result["control"]["correct"], result["control"]


def test_planted_family_without_its_norms_reads_not_correct(planted):
    """The program applies the norms, this reference does not."""
    result = tiny.run_tiny("qknorm_toy_blind-chat")
    assert not result["correct"], result["check"]
