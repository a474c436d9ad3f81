"""The comparison that decides ``correct``, at a size the CPU holds.

Both configurations run the whole harness (session path, fused kernel in
interpret mode, prefix cache for the doc cells) against the float32
reference. The control, the reference computed in float8 at the positions
the program served, reads above the sound program. (The program's own int8
KV flavour, ``fused_q8``, moves the widest gap too little to tell apart at
this size; its chip readings are in PERF.md.) Each fault a serving cell can have, planted
under the harness, turns ``correct`` false: a decode step that returns its
state unchanged, and a token altered where the wave produces it. A cell on
one chip that serves no batch mean has neither a dropped half of a batch nor
an exchange between chips to leave out.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import tiny

CELLS = [w["name"] for w in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _readings(result):
    return {k: v["value"] for k, v in result["check"].items()}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_matches_the_reference(cell):
    result = tiny.run_tiny(cell)
    assert result["correct"], result["check"]
    assert result["info"]["checked_tokens"] > 20
    assert result["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_float8_control_reads_above_the_program(cell):
    """The reference computed in float8, at the positions the program
    served, puts other tokens first than the float32 reference."""
    result = tiny.run_tiny(cell, control=True)
    sound = _readings(result)
    control = {k: v["value"] for k, v in result["control"]["check"].items()}
    assert control["gap"] > 3 * sound["gap"]
    assert result["correct"] and not result["control"]["correct"]


def _wrap_wave(session, change):
    """Plant ``change(stacked_in, out, stacked_out)`` under every wave the
    session builds. ``stacked_in`` is a copy of the wave's input taken
    before the wave runs, so it outlives a wave that donates its input."""
    build = session._wave_for

    def wave_for(fn, sampled=False):
        wave = build(fn, sampled)

        def faulty(stacked, tokens, rows):
            before = jax.tree.map(jnp.copy, stacked)
            out, new, rows = wave(stacked, tokens, rows)
            out, new = change(before, out, new)
            return out, new, rows

        faulty.returns_tokens = True
        return faulty

    session._wave_for = wave_for


def _state_unchanged(session):
    _wrap_wave(session, lambda old, out, new: (out, old))


def _token_altered(session):
    vocab = session.backend.cfg.vocab
    _wrap_wave(session, lambda old, out, new: ((out + 1) % vocab, new))


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
@pytest.mark.parametrize("cell", ["yi6b-doc8k-dense", "yi6b-chat-short"])
def test_fault_turns_correct_false(cell, fault):
    result = tiny.run_tiny(cell, session_hook=fault)
    assert not result["correct"], result["check"]


def _donating(session):
    """Every wave deletes its input state once it has run, as a wave that
    donates that state leaves it."""
    build = session._wave_for

    def wave_for(fn, sampled=False):
        wave = build(fn, sampled)

        def donated(stacked, tokens, rows):
            result = wave(stacked, tokens, rows)
            for leaf in jax.tree.leaves(stacked):
                leaf.delete()
            return result

        donated.returns_tokens = True
        return donated

    session._wave_for = wave_for


def test_state_unchanged_fault_survives_a_donated_wave():
    """The fault returns its own copy of the input, not the deleted input:
    the run goes on to its end and reads not correct."""

    def hook(session):
        _donating(session)
        _state_unchanged(session)

    result = tiny.run_tiny("yi6b-chat-short", session_hook=hook)
    assert not result["correct"], result["check"]
    assert result["info"]["checked_tokens"] > 20


def test_fault_hook_reaches_the_wave():
    seen = []

    def spy(session):
        _wrap_wave(session, lambda old, out, new: (seen.append(1) or out,
                                                   new))

    tiny.run_tiny("yi6b-chat-short", session_hook=spy)
    assert seen
    assert jnp.int32  # the wrapped wave keeps the token dtype
