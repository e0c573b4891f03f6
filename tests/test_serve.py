"""BatchedServer regression tests: per-slot decode positions, and each
token fed once.

The scalar-``pos`` server passed ``max(slot_pos)`` to every slot, writing
all KV caches at the same index — wrong (and cache-corrupting) as soon as
slots sit at different sequence depths.  A later fault fed the prompt's
last token twice (prefill, then again in the first step).  The stub-decode
tests pin the tokens and positions the scheduling loop passes; the slow
JAX test checks batched decode with ragged slots matches each request
decoded alone.
"""
import dataclasses

import numpy as np
import pytest

from repro.launch.serve import BatchedServer, Request


def _stub_server(slots=3, vocab=8, max_len=64):
    calls = []

    def stub(params, state, tokens, pos):
        calls.append((np.asarray(tokens).copy(), np.asarray(pos).copy()))
        return np.zeros((slots, vocab), np.float32), state

    server = BatchedServer(cfg=None, batch_slots=slots, max_len=max_len,
                           decode_fn=stub, record_events=True)
    server.load(None)
    return server, calls


def test_step_passes_per_slot_positions():
    server, calls = _stub_server(slots=3)
    server.admit(Request(0, np.array([1, 2, 3], np.int32), max_new=4))
    server.admit(Request(1, np.array([7], np.int32), max_new=4))
    calls.clear()
    server.step()
    tokens, pos = calls[-1]
    # regression: slot 0 feeds its last prompt token at its own position 2,
    # slot 1 its only one at 0 — the old scalar code passed max(slot_pos)
    # = 2 for both
    assert pos.shape == (3,)
    assert list(pos) == [2, 0, 0]
    assert list(tokens[:2]) == [3, 7]
    server.step()
    _, pos = calls[-1]
    assert list(pos) == [3, 1, 0]


def test_admit_prefill_preserves_other_slot_positions():
    server, calls = _stub_server(slots=2)
    server.admit(Request(0, np.array([1, 2, 3], np.int32), max_new=8))
    server.step()                      # slot0 advances to 3
    calls.clear()
    server.admit(Request(1, np.array([5, 6, 7], np.int32), max_new=8))
    # during slot1's prefill, slot0 must keep its own position (3), not be
    # dragged to the prefill token index (the cache-corruption regression)
    assert [list(pos) for _, pos in calls] == [[3, 0], [3, 1]]
    assert list(server.slot_pos) == [3, 2]


def test_prefill_targets_only_the_admitted_slot():
    server, calls = _stub_server(slots=2)
    server.admit(Request(0, np.array([9, 8, 7], np.int32), max_new=2))
    for tokens, _ in calls:
        assert tokens[1] == 0          # other slot sees padding tokens only
    # the last prompt token is left for the first decode step
    assert [t[0] for t, _ in calls] == [9, 8]


def test_each_token_fed_once_at_its_position():
    """A request's model input is its prompt and then its served tokens,
    each once, at positions 0, 1, 2, ... (the old loop fed the prompt's
    last token a second time, at position len(prompt))."""
    slots, vocab = 2, 16
    fed = []

    def stub(params, state, tokens, pos):
        fed.append((int(tokens[0]), int(pos[0])))
        logits = np.zeros((slots, vocab), np.float32)
        logits[:, (int(tokens[0]) * 3 + 1) % vocab] = 1.0
        return logits, state

    server = BatchedServer(cfg=None, batch_slots=slots, max_len=64,
                           decode_fn=stub)
    prompt = [5, 9, 2, 11]
    r = Request(0, np.array(prompt, np.int32), max_new=5)
    server.admit(r)
    while not r.done:
        server.step()
    seq = prompt + r.out[:-1]
    assert fed == [(t, i) for i, t in enumerate(seq)]
    assert len(r.out) == 5


def test_events_and_metrics_recorded():
    server, _ = _stub_server(slots=2)
    server.admit(Request(0, np.array([1], np.int32), max_new=2))
    server.admit(Request(1, np.array([2, 3], np.int32), max_new=1))
    server.step()
    server.step()
    assert server.events[0] == ("admit", 0)
    assert server.events[1] == ("admit", 1)
    assert server.events[2] == ("step", (0, 1))
    assert ("finish", 1) in server.events
    assert ("finish", 0) in server.events
    finished = [e for e in server.events if e[0] == "finish"]
    assert finished == [("finish", 1), ("finish", 0)]


def test_slot_reuse_after_finish():
    server, calls = _stub_server(slots=1)
    r0 = Request(0, np.array([1], np.int32), max_new=1)
    server.admit(r0)
    server.step()
    assert r0.done and server.slot_req == [None]
    assert r0.t_done >= r0.t_first >= r0.t_admit
    r1 = Request(1, np.array([2], np.int32), max_new=1)
    assert server.admit(r1)            # freed slot is reusable
    server.step()
    assert r1.done


def test_non_finite_logits_are_flagged():
    slots, vocab = 2, 8

    def stub(params, state, tokens, pos):
        logits = np.zeros((slots, vocab), np.float32)
        logits[1, 3] = np.nan
        return logits, state

    server = BatchedServer(cfg=None, batch_slots=slots, max_len=16,
                           decode_fn=stub)
    ok, bad = (Request(i, np.array([1], np.int32), max_new=2)
               for i in range(2))
    server.admit(ok)
    server.admit(bad)
    server.step()
    assert ok.finite and not bad.finite


@pytest.mark.slow
def test_ragged_batched_decode_matches_solo():
    """Numeric regression: slots at different depths decode exactly as if
    each request ran alone (requires the per-slot cache writes)."""
    import jax
    import jax.numpy as jnp

    from repro.core.config import get_arch
    from repro.models import api

    spec = get_arch("qwen1.5-0.5b")
    cfg = dataclasses.replace(spec.smoke, param_dtype="float32",
                              compute_dtype="float32")
    params = api.init_params(jax.random.key(0), cfg)
    max_len = 16
    tok_a = [3, 11, 4, 8]
    tok_b = [6, 2]

    def solo(tokens):
        st = api.allocate_decode_state(cfg, 1, max_len)
        outs = []
        for p, t in enumerate(tokens):
            lg, st = api.decode_step(params, cfg, st,
                                     jnp.asarray([t], jnp.int32),
                                     jnp.asarray([p], jnp.int32))
            outs.append(np.asarray(lg)[0])
        return outs

    solo_a, solo_b = solo(tok_a), solo(tok_b)

    st = api.allocate_decode_state(cfg, 2, max_len)
    pos = np.zeros(2, np.int32)
    got = {0: [], 1: []}
    ia = ib = 0
    for members in [(0,), (0,), (0, 1), (0, 1)]:   # slot1 joins 2 steps late
        tokens = np.zeros(2, np.int32)
        if 0 in members:
            tokens[0] = tok_a[ia]
        if 1 in members:
            tokens[1] = tok_b[ib]
        lg, st = api.decode_step(params, cfg, st, jnp.asarray(tokens),
                                 jnp.asarray(pos, jnp.int32))
        lg = np.asarray(lg)
        if 0 in members:
            got[0].append(lg[0])
            pos[0] += 1
            ia += 1
        if 1 in members:
            got[1].append(lg[1])
            pos[1] += 1
            ib += 1

    for want, have in zip(solo_a, got[0]):
        np.testing.assert_allclose(have, want, atol=1e-4)
    for want, have in zip(solo_b, got[1]):
        np.testing.assert_allclose(have, want, atol=1e-4)
