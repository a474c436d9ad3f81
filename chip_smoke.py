"""Chip smoke test: serve yi-6b at its published widths on a TPU.

    python3 chip_smoke.py               # one chip: the serving session
    python3 chip_smoke.py --four-chips  # 2x2 mesh session vs one device

The one-chip run builds yi-6b at its published widths and depth (d_model
4096, 32/4 heads, d_ff 11008, vocab 64000, 32 layers) with weights drawn
from a fixed seed, and serves a few requests through
``repro.launch.serve.build_session`` on the sectored path with the fused
Pallas kernel. It checks that every request completes with in-vocabulary
tokens, that sectored steps ran, that the compiled wave holds the Mosaic
kernel, and that one teacher-forced step of the fused kernel (and of its
int8 flavour) agrees with the dispatch path within stated tolerances.

``--four-chips`` runs only the mesh path: the same session on a 2x2 mesh
(slots over ``data``, KV pages over ``model``) against the one-device
session, in one process; their token streams must be identical.

The script runs in one process and never falls back to the CPU: with no
TPU it exits non-zero before any work. Any failed check exits non-zero.
The last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.kernels import quantized_kv  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_session  # noqa: E402
from repro.models import model  # noqa: E402
from repro.runtime import sectored_decode  # noqa: E402
from repro.sample import SamplerRows  # noqa: E402
from repro.serve import Request  # noqa: E402
from repro.serve.backend import fused_wave_program  # noqa: E402

SEED = 0
ARCH = "yi-6b"
SEQ_LEN = 1000  # KV padded to 1024 tokens (8 pages of 128) per slot
MAX_BATCH = 4
NEW_TOKENS = 16
# two prompt lengths (two prefill compiles); 700 tokens fill 6 pages, so
# the 4 + 1-probe page budget really skips one of them
PROMPT_LENS = (700, 16, 16, 700, 16, 16)
# teacher-forced one-step agreement with the dispatch path, max |d logprob|.
# The fused kernel runs the dispatch path's operands and reduction axes, and
# reads 0.0 on a v5e; int8 KV reads ~0.033 there. The fused limit sits
# between the two, and fused_q8 is the control that must exceed it.
FUSED_LOGPROB_TOL = 1e-3
Q8_LOGPROB_TOL = quantized_kv.LOGPROB_TOL
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(ok: bool, what: str) -> None:
    if not ok:
        print(f"FAILED: {what}", file=sys.stderr, flush=True)
        sys.exit(1)


def say(line: str) -> None:
    print(line, flush=True)


def build_params(cfg):
    """Weights from the fixed seed, built in one jitted program (eager
    init would hold a transient copy of the largest stacked weight)."""
    t0 = time.perf_counter()
    params = jax.jit(model.init_params, static_argnums=0)(
        cfg, jax.random.key(SEED))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    say(f"params: {n} parameters, {nbytes} bytes ({cfg.dtype}), "
        f"built in {time.perf_counter() - t0:.1f} s")
    return params


def serve(cfg, params, *, kernel: str, mesh=None):
    """Submit the request mix to a fresh session and drain it."""
    sess = build_session(cfg, params, max_batch=MAX_BATCH,
                         true_sectored=True, kernel=kernel,
                         policy="sectored", seq_len=SEQ_LEN, mesh=mesh)
    rng = np.random.default_rng(SEED)
    handles = [sess.submit(Request(
        rid, rng.integers(0, cfg.vocab, size=n).astype(np.int32),
        max_new_tokens=NEW_TOKENS)) for rid, n in enumerate(PROMPT_LENS)]
    t0 = time.perf_counter()
    stats = sess.run_until_drained()
    wall = time.perf_counter() - t0
    for h in handles:
        toks = h.peek()
        check(h.done and len(toks) == NEW_TOKENS,
              f"request {h.rid} finished with {len(toks)} of {NEW_TOKENS} "
              f"tokens")
        check(all(0 <= t < cfg.vocab for t in toks),
              f"request {h.rid} emitted a token outside [0, {cfg.vocab})")
        check(bool(np.all(np.isfinite(h.logprobs()))),
              f"request {h.rid} has a non-finite logprob")
    return sess, handles, stats, wall


def log_softmax(logits) -> np.ndarray:
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


def one_chip(cfg) -> None:
    say(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"layers={cfg.n_layers} (published depth, no cut)")
    params = build_params(cfg)
    sess, handles, stats, wall = serve(cfg, params, kernel="fused")
    say(f"session: kernel=fused policy=sectored max_batch={MAX_BATCH} "
        f"seq_len={SEQ_LEN} prompts={list(PROMPT_LENS)} "
        f"new_tokens={NEW_TOKENS} served in {wall:.1f} s")
    say(f"served: completed={stats['completed']} waves={stats['waves']} "
        f"decode_steps={stats['decode_steps']} "
        f"sectored_steps={stats['sectored_steps']}")
    check(stats["completed"] == len(PROMPT_LENS), "not every request completed")
    check(stats["sectored_steps"] > 0, "no sectored decode step ran")

    backend = sess.backend
    step = backend.sectored_fn_for(None)
    k_pages = backend.k_for(None)
    # the session's wave program, compiled again (a persistent-cache hit
    # when the cache is on) to read what the chip runs
    jitted, bound = fused_wave_program(step)
    wave_text = jitted.lower(
        bound, sess.batched, jnp.zeros((MAX_BATCH, 1, 1), jnp.int32),
        SamplerRows.init(MAX_BATCH)).compile().as_text()
    check("tpu_custom_call" in wave_text,
          "the compiled decode wave holds no Mosaic kernel")
    say("wave: tpu_custom_call present in the compiled decode wave")

    # one teacher-forced step from the same prefilled state per flavour
    prompt = np.random.default_rng(SEED + 1).integers(
        0, cfg.vocab, size=PROMPT_LENS[0]).astype(np.int32)
    logits0, state = backend.prefill_fn(prompt[None, :])
    token = jnp.argmax(logits0, axis=-1).astype(jnp.int32)[:, None]
    steps = {"fused": step}
    for kernel in ("dispatch", "fused_q8"):
        steps[kernel] = sectored_decode.make_serving_fns(
            cfg, params=params, seq_len=SEQ_LEN,
            kernel=kernel).sectored_fn_for(None)
    logits = {k: np.asarray(fn(state, token)[0], np.float32)
              for k, fn in steps.items()}
    for k, v in logits.items():
        check(bool(np.all(np.isfinite(v))), f"{k} step logits are not finite")
    ref = log_softmax(logits["dispatch"])
    d_fused = float(np.max(np.abs(log_softmax(logits["fused"]) - ref)))
    d_q8 = float(np.max(np.abs(log_softmax(logits["fused_q8"]) - ref)))
    d_logit = float(np.max(np.abs(logits["fused"] - logits["dispatch"])))
    say(f"step check (prompt {PROMPT_LENS[0]}, {k_pages}+1 of "
        f"{backend.pages} pages): fused vs dispatch max|dlogprob|="
        f"{d_fused!r} (tol {FUSED_LOGPROB_TOL}) max|dlogit|={d_logit!r}; "
        f"fused_q8 vs dispatch max|dlogprob|={d_q8!r} (tol {Q8_LOGPROB_TOL})")
    check(d_fused <= FUSED_LOGPROB_TOL, "fused step disagrees with dispatch")
    check(d_q8 <= Q8_LOGPROB_TOL, "fused_q8 step disagrees with dispatch")
    check(d_q8 > FUSED_LOGPROB_TOL,
          "control: int8 KV error is within the fused limit, so the limit "
          "cannot tell a lossy kernel from a sound one")


def four_chips(cfg) -> None:
    # every device holds one copy of the weights: the mesh's replica on
    # device 0 is the one-device session's buffer, and the donor device's
    # prefill reads its own replica
    say(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"layers={cfg.n_layers} (published depth, no cut)")
    params = build_params(cfg)
    # Mosaic kernels are not partitioned automatically, so the mesh
    # session and its one-device reference run the dispatch flavour
    _, single, _, wall1 = serve(cfg, params, kernel="dispatch")
    sess, meshed, stats, wall4 = serve(cfg, params, kernel="dispatch",
                                       mesh="2x2")
    say(f"served: one device {wall1:.1f} s; mesh "
        f"{'x'.join(map(str, sess.mesh.devices.shape))} {wall4:.1f} s, "
        f"sectored_steps={stats['sectored_steps']}")
    check(stats["sectored_steps"] > 0, "no sectored decode step ran")
    holders = set()
    for leaf in jax.tree.leaves(sess.batched.kv):
        check(not leaf.sharding.is_fully_replicated,
              f"a KV buffer is replicated: {leaf.sharding}")
        holders |= {s.device for s in leaf.addressable_shards
                    if s.data.size > 0}
    say(f"placement: KV sharded {sess.batched.kv.k.sharding.spec} over "
        f"{len(holders)} devices")
    check(len(holders) == len(jax.devices()), "some device holds no slot")
    worst = 0.0
    for a, b in zip(single, meshed):
        n = min(len(a.peek()), len(b.peek()))
        if n:
            worst = max(worst, float(np.max(np.abs(
                np.asarray(a.logprobs()[:n]) - np.asarray(b.logprobs()[:n])))))
    say(f"streams: max |dlogprob| between one device and the mesh = "
        f"{worst!r}")
    for a, b in zip(single, meshed):
        check(a.peek() == b.peek(),
              f"request {a.rid}: mesh tokens differ from one device")
    say("streams: mesh tokens identical to one device for every request")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh session and the one-device "
                         "session it is compared with")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"FAILED: no TPU: JAX found {len(devices)} {dev.platform} "
              f"device(s)", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    check(len(devices) >= want, f"needs {want} chips, found {len(devices)}")
    cache = enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache}")
    t0 = time.perf_counter()
    cfg = configs.get(ARCH)
    if args.four_chips:
        four_chips(cfg)
    else:
        one_chip(cfg)
    peaks = []
    for d in devices[:want]:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use") or 0)
        say(f"memory: device {d.id} peak_bytes_in_use={peaks[-1]} "
            f"bytes_limit={stats.get('bytes_limit')}")
    check(all(peaks), "a device reported no peak_bytes_in_use")
    say(f"memory: largest peak_bytes_in_use={max(peaks)} over {want} "
        f"device(s)")
    say(f"time: compile_s={compile_s[0]:.1f} "
        f"wall_s={time.perf_counter() - t0:.1f}")
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
