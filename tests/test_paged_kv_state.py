"""The block manager's second kind of cache: per-sequence rows beside the
chains, prefix reuse by state snapshot, and the widened ``cache_spec()``
(one entry a token, one every ``n`` tokens, one a sequence). Pure Python
but for the pools' shapes."""

import numpy as np
import pytest

from mmlspark_tpu.dl.paged_kv import (OutOfBlocks, PagedKVManager,
                                      SequenceHandle, entry_kind,
                                      init_pools, pool_block_bytes,
                                      state_row_bytes)
from mmlspark_tpu.obs.metrics import MetricsRegistry

BL = 4
SPEC = ((((8,), "float32"), ((8,), "float32"),
         ((8,), "float32", ("every", 2))),
        (((2, 4, 4), "float32", "seq"),))


def _mgr(blocks=12, rows=3, **kw):
    reg = MetricsRegistry()
    return PagedKVManager(blocks, BL, state_slots=rows, state_row_bytes=128,
                          registry=reg, service="t", **kw), reg


def _value(reg, name):
    return next(m for m in reg.metrics(name) if m.name == name).value(
        service="t")


def _prefill(mgr, seq_id, prompt, snapshot=True):
    """What the prefill executor does with a handle, on the host."""
    h = mgr.allocate(seq_id, prompt)
    mgr.restored(seq_id)
    row = mgr.take_snapshot(seq_id) if snapshot else None
    mgr.advance(seq_id, h.prompt_len - h.length)
    mgr.publish(seq_id)
    return h, row


@pytest.mark.parametrize("entry,kind", [
    (((8,), "float32"), ("token", 1)),
    (((8,), "float32", "token"), ("token", 1)),
    (((8,), "float32", ("every", 16)), ("every", 16)),
    (((2, 4, 4), "float32", "seq"), ("seq", 0))])
def test_entry_kinds(entry, kind):
    assert entry_kind(entry) == kind


def test_an_unknown_kind_is_refused():
    with pytest.raises(ValueError):
        entry_kind(((8,), "float32", "window"))


def test_pools_take_the_shape_of_their_kind():
    pools = init_pools(SPEC, 5, BL, state_slots=3)
    assert [p.shape for p in pools[0]] == [(5, 4, 8), (5, 4, 8), (5, 2, 8)]
    assert pools[1][0].shape == (4, 2, 4, 4)          # row 0 is the trash row


def test_a_block_is_priced_without_the_rows_and_a_row_without_the_blocks():
    chained = (SPEC[0],)
    assert pool_block_bytes(SPEC, BL) == pool_block_bytes(chained, BL)
    assert state_row_bytes(SPEC) == 2 * 4 * 4 * 4
    assert state_row_bytes(chained) == 0


def test_every_n_has_to_divide_the_block():
    with pytest.raises(ValueError):
        init_pools(((((8,), "float32", ("every", 3)),),), 4, BL)


def test_a_sequence_takes_a_row_and_gives_it_back():
    mgr, reg = _mgr()
    a = mgr.allocate("a", range(1, 7))
    b = mgr.allocate("b", range(11, 17))
    assert {a.state_row, b.state_row} <= {1, 2, 3} \
        and a.state_row != b.state_row
    assert list(mgr.state_rows(["a", None, "b"])) == [a.state_row, 0,
                                                      b.state_row]
    assert _value(reg, "kv_state_slots_used") == 2
    assert _value(reg, "kv_state_bytes") == 2 * 128
    mgr.release("a")
    assert _value(reg, "kv_state_slots_used") == 1
    assert mgr.allocate("c", range(21, 27)).state_row is not None


def test_no_row_left_is_out_of_blocks_and_leaks_nothing():
    mgr, _ = _mgr(rows=1)
    mgr.allocate("a", range(1, 7))
    used = mgr.stats()["used"]
    with pytest.raises(OutOfBlocks):
        mgr.allocate("b", range(11, 17))
    assert mgr.stats()["used"] == used


def test_a_prefix_is_reused_only_up_to_a_snapshot():
    mgr, reg = _mgr()
    doc = list(range(1, 13))                          # three whole chunks
    h, row = _prefill(mgr, "doc", doc)
    assert h.snapshot_at is None and row is not None  # taken, then indexed
    assert _value(reg, "kv_state_snapshots") == 1
    mgr.release("doc")
    hit = mgr.allocate("q", doc + [50, 51])
    assert (hit.reused_tokens, hit.restore_row) == (12, row)
    assert hit.snapshot_at is None                    # no new whole chunk
    mgr.restored("q")
    assert _value(reg, "kv_state_restores_total") == 1
    # a prompt that shares two of the three chunks finds no snapshot at
    # that boundary: a miss, and blocks of its own
    part = mgr.allocate("p", doc[:8] + [60, 61, 62, 63, 64])
    assert (part.reused_tokens, part.restore_row) == (0, None)
    assert not set(part.chain) & set(hit.chain)


def test_without_a_snapshot_the_new_blocks_are_indexed_but_not_reusable():
    mgr, _ = _mgr()
    doc = list(range(1, 9))
    _prefill(mgr, "doc", doc, snapshot=False)
    mgr.release("doc")
    assert mgr.stats()["indexed_prefixes"] == 2
    assert mgr.allocate("q", doc + [9]).reused_tokens == 0


def test_a_prompt_that_is_all_prefix_stops_short_of_its_last_token():
    """The state cannot be rewound to re-feed the last token, so a hit on
    the whole prompt backs off to an earlier snapshot, or to none."""
    mgr, _ = _mgr()
    doc = list(range(1, 9))
    _prefill(mgr, "doc", doc)
    mgr.release("doc")
    again = mgr.allocate("again", doc)
    assert (again.reused_tokens, again.restore_row) == (0, None)


def test_the_snapshot_is_cut_at_the_prompts_last_whole_chunk():
    mgr, _ = _mgr()
    h = mgr.allocate("a", range(1, 11))               # 2 chunks and 2 tokens
    assert h.snapshot_at == 8
    short = mgr.allocate("b", range(21, 24))          # no whole chunk
    assert short.snapshot_at is None


def test_a_pinned_snapshot_outlives_the_rows_pressure():
    mgr, reg = _mgr(rows=2)
    doc = list(range(1, 9))
    _, row = _prefill(mgr, "doc", doc)
    mgr.release("doc")
    hit = mgr.allocate("q", doc + [9])                # pins the snapshot
    assert hit.restore_row == row
    with pytest.raises(OutOfBlocks):                  # its row is not taken
        mgr.allocate("other", range(31, 34))
    mgr.restored("q")
    other = mgr.allocate("other", range(31, 34))      # now it is
    assert other.state_row == row
    assert _value(reg, "kv_state_snapshot_evictions_total") == 1
    mgr.release("q")
    assert mgr.allocate("q2", doc + [9]).reused_tokens == 0   # a miss


def test_a_snapshot_never_restored_goes_before_one_that_was():
    """Requests whose own chunks nobody asks for again leave snapshots
    too; they must not push out the documents' (first chip sweep, PR 33:
    at a block of 128 tokens they did, a document was prefilled anew a
    request and the pool ran out)."""
    mgr, _ = _mgr(blocks=40, rows=3)
    doc = list(range(1, 9))
    _prefill(mgr, "doc", doc)
    mgr.release("doc")
    for i in range(4):                 # each: the document and a chunk
        own = doc + [100 + 10 * i + j for j in range(5)]
        h, _ = _prefill(mgr, f"q{i}", own)
        assert h.reused_tokens == 8, i         # the document's is there
        mgr.release(f"q{i}")
    assert mgr.stats()["state_snapshots"] == 2    # the document's, the last


def test_a_snapshot_goes_with_the_block_it_ends():
    mgr, reg = _mgr(blocks=5)                         # 4 blocks to hand out
    doc = list(range(1, 9))
    _prefill(mgr, "doc", doc)
    mgr.release("doc")                                # 2 cached blocks
    mgr.allocate("big", range(101, 115))              # needs all 4
    assert _value(reg, "kv_state_snapshots") == 0
    assert _value(reg, "kv_state_snapshot_evictions_total") == 1


def test_rows_and_blocks_draw_on_one_budget():
    mgr, _ = _mgr(blocks=12, rows=3, state_row_blocks=2)
    assert mgr.block_budget == 11 + 3 * 2
    doc = list(range(1, 9))
    _prefill(mgr, "doc", doc)
    mgr.release("doc")                # cached: 2 blocks and a snapshot row
    assert mgr.stats()["cached"] == 2
    evicted = mgr.set_block_budget(3)
    assert evicted == 2 and mgr.stats()["state_snapshots"] == 0


def test_handoff_carries_the_row():
    mgr, _ = _mgr()
    h, _ = _prefill(mgr, "a", range(1, 7), snapshot=False)
    state = mgr.export_seq("a")
    assert state["state_row"] == h.state_row
    assert SequenceHandle.from_state(state).state_row == h.state_row
    assert mgr.adopt(state).state_row == h.state_row
    other, _ = _mgr()
    with pytest.raises(ValueError):
        other.adopt({**state, "chain": []})           # a row nobody holds


def test_a_manager_without_rows_is_the_one_it_was():
    reg = MetricsRegistry()
    mgr = PagedKVManager(8, BL, registry=reg, service="t")
    doc = list(range(1, 9))
    h = mgr.allocate("doc", doc)
    assert (h.state_row, h.snapshot_at) == (None, None)
    mgr.advance("doc", 8)
    mgr.publish("doc")
    mgr.release("doc")
    assert mgr.allocate("q", doc).reused_tokens == 8  # the whole prompt
    assert list(mgr.state_rows(["q"])) == [0]
    assert np.asarray(mgr.block_rows(["q"], 3)).shape == (1, 3)


# ------------------------------------------- two arrays a sequence a layer
#: a layer that keeps TWO per-sequence arrays of different shape and type
#: (a float32 state and a bfloat16 convolution tail), beside a layer of
#: chained keys and values
TWO = ((((2, 4, 4), "float32", "seq"), ((3, 16), "bfloat16", "seq")),
       (((8,), "float32"), ((8,), "float32")),
       (((2, 4, 4), "float32", "seq"), ((3, 16), "bfloat16", "seq")))


def test_two_arrays_a_sequence_are_one_row_of_the_budget():
    """A row is every ``"seq"`` array of every layer: priced together,
    allocated together (one pool an entry, the same row in each), and a
    block is priced without them."""
    import jax.numpy as jnp

    from mmlspark_tpu.dl.paged_kv import copy_state_rows
    row = 2 * (2 * 4 * 4 * 4 + 3 * 16 * 2)
    assert state_row_bytes(TWO) == row
    assert pool_block_bytes(TWO, BL) == pool_block_bytes((TWO[1],), BL)
    pools = init_pools(TWO, 5, BL, state_slots=3)
    assert [p.shape for p in pools[0]] == [(4, 2, 4, 4), (4, 3, 16)]
    assert [str(p.dtype) for p in pools[2]] == ["float32", "bfloat16"]
    assert [p.shape for p in pools[1]] == [(5, 4, 8), (5, 4, 8)]
    # a snapshot taken, a snapshot restored: both arrays of both layers
    marked = tuple(tuple(p.at[1].set(5) for p in layer) if i != 1 else layer
                   for i, layer in enumerate(pools))
    out = copy_state_rows(TWO, marked, jnp.asarray([1]), jnp.asarray([3]))
    for i in (0, 2):
        for p in out[i]:
            assert float(p[3].min()) == 5.0 and float(p[2].max()) == 0.0
    assert out[1][0] is marked[1][0]
    # the manager's gauge counts a row's bytes whole, live and snapshot
    reg = MetricsRegistry()
    blocks = -(-row // pool_block_bytes(TWO, BL))
    mgr = PagedKVManager(12, BL, state_slots=3, state_row_bytes=row,
                         state_row_blocks=blocks, registry=reg, service="t")
    assert mgr.block_budget == 11 + 3 * blocks
    doc = list(range(1, 9))
    h, snap = _prefill(mgr, "doc", doc)
    assert snap is not None and snap != h.state_row
    assert _value(reg, "kv_state_bytes") == 2 * row
    mgr.release("doc")
    assert _value(reg, "kv_state_bytes") == row       # the snapshot stays
    # a prefix hit names the snapshot's row to restore from, pinned
    h2 = mgr.allocate("q", doc + [9, 10])
    assert h2.reused_tokens == 8 and h2.restore_row == snap
    mgr.restored("q")
    assert _value(reg, "kv_state_restores_total") == 1
    # recycled under the rows' pressure: then the prefix is a miss
    mgr.allocate("a", [21, 22, 23])
    mgr.allocate("b", [31, 32, 33])                   # takes the snapshot's
    assert _value(reg, "kv_state_snapshot_evictions_total") == 1
    mgr.release("q")
    assert mgr.allocate("q2", doc + [9]).reused_tokens == 0
