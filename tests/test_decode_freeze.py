"""The decode chunk's freeze contract: rows outside ``live`` (inactive,
fork-paused, or EOS-finished inside the chunk) are frozen at each write
of the step — K/V append, token id, counters, resync — never by a select
over the whole state after it.

* **contract** — every frozen row's ``merged()`` state and physical KV
  are bit-identical to the input, and live rows' tokens and state equal
  the former formulation (step every row, then ``where_rows``), for
  tconst and the dense LM on every layout.
* **structure** — the scan body of ``decode_chunk`` holds no
  ``select_n`` as large as a KV field or the token buffer, so the
  whole-state freeze cannot come back unnoticed.
* **counter** — the scheduler's chunk ``StepStats.frozen`` counts the
  slots masked out of each dispatch.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parity
from repro.models import layouts as LT
from repro.models.api import build_decode, decode_chunk, sample_tokens
from repro.serving.scheduler import SlotScheduler
from repro.serving.session import Session

SLOTS, MAX_LEN, STEPS = 4, 64, 6


def _former_chunk(decode, params, state, token, key, temperature, active,
                  n_steps, eos):
    """The formulation the chunk replaced: step EVERY row (resync
    included), then freeze non-live rows by a per-row select over the
    whole state.  Per-slot keys only."""
    def body(carry, _):
        state, tok, key = carry
        done = state.bookkeeping["done"]
        live = jnp.logical_and(active, jnp.logical_not(done))
        logits, new_state = decode.step(params, state, tok)
        pair = jax.vmap(jax.random.split)(key)
        key = jnp.where(live[:, None], pair[:, 0], key)
        nxt = sample_tokens(logits, temperature, pair[:, 1])
        nxt = jnp.where(live, nxt, tok)
        new_state = new_state.where_rows(live, state)
        hit = jnp.logical_and(live, jnp.logical_and(eos >= 0, nxt == eos))
        new_state = new_state.with_bookkeeping(
            done=jnp.logical_or(done, hit))
        return (new_state, nxt, key), nxt

    (state, _, key), toks = jax.lax.scan(
        body, (state, token, key), None, length=n_steps)
    return jnp.moveaxis(toks, 0, 1), state, key


def _admitted(fam, kind):
    """A 4-slot state: slot 0 live and crossing a W_og boundary inside
    the chunk (tconst), slot 1 inactive, slot 2 live (its EOS is set
    later), slot 3 live (LM) or inactive standing at ``gen_len ==
    W_og`` (tconst: due for a resync it must not get)."""
    cfg, _, params = parity.family(fam)
    decode = build_decode(cfg, parity.layout_spec(kind, pool_pages=None))
    w = cfg.tconst.w_og
    lens = (w + w - 3, 13, 2 * w + 1, 2 * w) if fam == "tconst" \
        else (11, 13, 17, 9)
    state = decode.init_state(SLOTS, MAX_LEN)
    admit = jax.jit(decode.prefill_into_slot)
    token = []
    for slot, prompt in enumerate(parity.make_prompts(cfg, lens, seed=5)):
        logits, state = admit(params, state, np.int32(slot),
                              jnp.asarray(prompt))
        token.append(int(jnp.argmax(logits)))
    active = np.array([True, False, True, fam != "tconst"])
    if fam == "tconst":
        assert int(state.bookkeeping["gen_len"][3]) == w
    return cfg, decode, params, state, jnp.asarray(token, jnp.int32), active


def _rows(arr, rows, axis):
    return np.take(np.asarray(arr), rows, axis=axis)


def _frozen_physical(state, rows):
    """Physical KV of ``rows``: batch-axis rows, or for paged fields the
    pool pages the rows' own page-table entries map."""
    out = {}
    lay = state.layout
    for f, arr in state.kv.items():
        la = lay._length_axis(f) if isinstance(lay, LT.PagedLayout) \
            else None
        if la is None:
            out[f] = _rows(arr, rows, lay._axis(f, state.axes))
        else:
            pages = np.asarray(state.bookkeeping[LT.PAGE_TABLE])[rows]
            out[f] = np.take(np.asarray(arr), pages.reshape(-1), axis=la - 1)
    return out


@pytest.mark.parametrize("fam,kind", [
    ("tconst", "dense"), ("lm", "dense"), ("lm", "paged"), ("lm", "int8"),
    ("lm", "paged_int8")])
def test_decode_chunk_freezes_rows_at_the_write(fam, kind):
    cfg, decode, params, state, token, active = _admitted(fam, kind)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(SLOTS))
    temps = jnp.zeros((SLOTS,), jnp.float32)
    act = jnp.asarray(active)
    former = jax.jit(functools.partial(_former_chunk, decode),
                     static_argnames=("n_steps",))
    chunk = jax.jit(functools.partial(decode_chunk, decode),
                    static_argnames=("n_steps",))

    # slot 2's EOS is a token it samples mid-chunk (first seen at step j)
    free, _, _ = former(params, state, token, keys, temps, act,
                        n_steps=STEPS, eos=jnp.full((SLOTS,), -1, jnp.int32))
    row = np.asarray(free)[2].tolist()
    j = next(i for i in range(1, STEPS - 1) if row[i] not in row[:i])
    eos = jnp.asarray([-1, -1, row[j], -1], jnp.int32)

    ref_toks, ref, ref_keys = former(params, state, token, keys, temps, act,
                                     n_steps=STEPS, eos=eos)
    toks, got, got_keys = chunk(params, state, token, keys, temps, act,
                                n_steps=STEPS, eos=eos)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref_toks))
    np.testing.assert_array_equal(np.asarray(got_keys), np.asarray(ref_keys))
    assert np.asarray(got.bookkeeping["done"]).tolist() == \
        [False, False, True, False]
    assert np.asarray(toks)[2, j:].tolist() == [row[j]] * (STEPS - j)

    # frozen rows: bit-identical to the input, logically and physically
    frozen = np.nonzero(~active)[0]
    before, after = state.merged(), got.merged()
    for f in before:
        np.testing.assert_array_equal(
            _rows(after[f], frozen, state.axes[f]),
            _rows(before[f], frozen, state.axes[f]), err_msg=f)
    phys_in = _frozen_physical(state, frozen)
    phys_out = _frozen_physical(got, frozen)
    for f in phys_in:
        np.testing.assert_array_equal(phys_out[f], phys_in[f], err_msg=f)

    # every row, live ones included, equals the former formulation
    for part in ("kv", "bookkeeping"):
        for f, v in getattr(ref, part).items():
            np.testing.assert_array_equal(
                np.asarray(getattr(got, part)[f]), np.asarray(v),
                err_msg=f"{part}[{f}]")


@pytest.mark.parametrize("fam,kind", [("tconst", "dense"), ("lm", "paged")])
def test_decode_chunk_scan_holds_no_whole_state_select(fam, kind):
    """No ``select_n`` in the chunk's scan body is as large as a KV
    field or the token buffer.  The resync's own row scatter (inside
    its ``cond`` branches, at most the synced rows) is not the freeze
    and is left out."""
    cfg, _, params = parity.family(fam)
    decode = build_decode(cfg, parity.layout_spec(kind, pool_pages=None))
    state = jax.eval_shape(lambda: decode.init_state(SLOTS, MAX_LEN))
    leaves = list(state.kv.values())
    if "tokens" in state.bookkeeping:
        leaves.append(state.bookkeeping["tokens"])
    smallest = min(int(np.prod(leaf.shape)) for leaf in leaves)
    i32 = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, s, t, k, tp, a, e: decode_chunk(
            decode, p, s, t, k, tp, a, n_steps=2, eos=e))(
        params, state, i32, jax.ShapeDtypeStruct((SLOTS, 2), jnp.uint32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.float32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_), i32)
    body, = [e.params["jaxpr"].jaxpr for e in closed.jaxpr.eqns
             if e.primitive.name == "scan"]
    in_resync = {id(e) for c in parity.walk_eqns(body)
                 if c.primitive.name == "cond"
                 for br in c.params["branches"]
                 for e in parity.walk_eqns(br.jaxpr)}
    big = [e.outvars[0].aval.shape for e in parity.walk_eqns(body)
           if e.primitive.name == "select_n" and id(e) not in in_resync
           and int(np.prod(e.outvars[0].aval.shape)) >= smallest]
    assert not big, f"whole-state select in decode_chunk: {big}"


def _spied(sched):
    """Record the ``active`` mask of every chunk dispatch."""
    masks, chunk = [], sched._chunk

    def spy(*args, **kw):
        masks.append(np.asarray(args[5]))
        return chunk(*args, **kw)
    sched._chunk = spy
    return masks


def test_chunk_stats_count_frozen_slots():
    """``StepStats(kind="chunk").frozen`` is the number of slots masked
    out of each dispatch: here first one empty slot and one slot paused
    for a copy-on-write fork the full pool cannot back, then 0 when
    every slot decodes."""
    cfg, _, params = parity.family("tlin")
    shared, = parity.shared_prompts(cfg, 1, common_len=32, tail_len=8,
                                    seed=4)
    # the second sharer's window is 3 tokens in (not due for a resync in
    # the first chunk, so it needs no fork yet); the small session fills
    # the pool, so the first sharer cannot fork and pauses
    second = np.concatenate([shared[:32],
                             parity.make_prompts(cfg, [3], seed=7)[0]])
    small = np.arange(1, 21, dtype=np.int32) % cfg.vocab_size
    sched = SlotScheduler(
        build_decode(cfg, parity.layout_spec("paged", pool_pages=8)),
        params, slots=4, max_len=128, chunk_size=4, prefix_sharing=True)
    masks = _spied(sched)
    sa, sb, sc = (sched.submit(Session(p, max_new_tokens=8))
                  for p in (shared, second, small))
    sched.step()
    assert [len(s.tokens) for s in (sa, sb, sc)] == [1, 5, 5]
    assert masks[0].tolist() == [False, True, True, False]
    sched.run()
    frozen = [s.frozen for s in sched.stats if s.kind == "chunk"]
    assert frozen[0] == 2
    assert frozen == [sched.slots - int(m.sum()) for m in masks]

    sched = SlotScheduler(build_decode(cfg), params, slots=2, max_len=128,
                          chunk_size=4)
    masks = _spied(sched)
    for p in parity.make_prompts(cfg, (9, 12)):
        sched.submit(Session(p, max_new_tokens=9))
    sched.run()
    assert all(m.all() for m in masks)
    assert [s.frozen for s in sched.stats if s.kind == "chunk"] == \
        [0] * len(masks)
