"""One timeline for the host path (ISSUE 26): the tracer's always-on
ring, the spans of ``TPUModel.transform`` and ``train_epoch``, and the
capture arithmetic (clock offset, idle time by span) on hand-made
captures — plain lists, no profiler."""

import math
import tempfile

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.obs import Span, tracer
from mmlspark_tpu.obs import timeline
from mmlspark_tpu.obs.tracing import RING_SIZE, Tracer, now_ns, wall_now


# ------------------------------------------------------------- the ring
class TestRing:
    def test_bounded_ordered_and_read_by_recent(self):
        tr = Tracer()
        for i in range(RING_SIZE + 10):
            tr.end_span(tr.start_span("r", parent=None, current=False, i=i))
        got = tr.recent()
        assert len(got) == RING_SIZE
        assert [s.attrs["i"] for s in got] == list(range(10, RING_SIZE + 10))
        assert [s.attrs["i"] for s in tr.recent(last=3)] == \
            [RING_SIZE + 7, RING_SIZE + 8, RING_SIZE + 9]
        assert tr.recent(last=0) == []

    def test_recent_filters_by_name_and_since(self):
        tr = Tracer()
        with tr.span("a"):
            pass
        mark = now_ns()
        with tr.span("b"):
            with tr.span("a"):
                pass
        assert [s.name for s in tr.recent()] == ["a", "a", "b"]
        assert len(tr.recent(name="a")) == 2
        assert [s.name for s in tr.recent(since=mark)] == ["a", "b"]
        assert [s.name for s in tr.recent(name="a", since=mark, last=5)] \
            == ["a"]

    def test_every_finished_span_lands_whatever_its_emit(self):
        tr = Tracer()
        tr.end_span(tr.start_span("quiet", current=False), emit=False)
        tr.emit_span("retro", parent=None, seconds=0.25)
        quiet, retro = tr.recent()
        assert (quiet.name, retro.name) == ("quiet", "retro")
        assert retro.end_ns - retro.start_ns == 250_000_000
        # ending twice appends once
        tr.end_span(quiet)
        assert len(tr.recent()) == 2

    def test_ns_fields_are_integers_on_the_wall_now_clock(self):
        tr = Tracer()
        before = wall_now()
        with tr.span("clocked") as sp:
            pass
        after = wall_now()
        assert isinstance(sp.start_ns, int) and isinstance(sp.end_ns, int)
        assert before <= sp.start_ns / 1e9 <= sp.end_ns / 1e9 <= after
        assert sp.seconds == (sp.end_ns - sp.start_ns) / 1e9
        assert sp.start_wall == pytest.approx(sp.start_ns / 1e9)

    def test_span_has_no_device_argument(self):
        import inspect
        assert "device" not in inspect.signature(Tracer.span).parameters
        # ``device=`` is an attr like any other now, not a switch
        with Tracer().span("x", device=True) as sp:
            pass
        assert sp.attrs == {"device": True}


# --------------------------------------------------- spans of the loops
class BatchNet(nn.Module):
    """Endpoints of every kind the column writer meets (at module level:
    a saved ``TPUModel`` pickles its module)."""
    @nn.compact
    def __call__(self, x, train=False):
        x = x.astype(jnp.float32)
        h = nn.Dense(4)(x)
        return {"pooled": h,
                "maps": jnp.tanh(h).reshape(-1, 2, 2, 1),
                "half": (h * 3).astype(jnp.bfloat16),
                "count": jnp.sum(x > 1, axis=1).astype(jnp.int32),
                # reads the whole minibatch, padded rows included
                "shifted": h + jnp.max(x)}


def _batch_net():
    module = BatchNet()
    return module, module.init(jax.random.PRNGKey(1), np.zeros((1, 6)))


def _children(root, since):
    return [s for s in tracer.recent(since=since)
            if s.parent_id == root.span_id]


class TestTPUModelSpans:
    N, BS = 20, 8                       # three minibatches, tail of 4

    def _transform(self):
        from mmlspark_tpu.core import DataFrame
        from mmlspark_tpu.dl.model import TPUModel
        model = TPUModel(model=_batch_net(), minibatchSize=self.BS,
                         inputCol="x", outputCol="y")
        x = np.arange(self.N * 6, dtype=np.float32).reshape(self.N, 6)
        mark = now_ns()
        out = model.transform(DataFrame({"x": x}))
        assert np.asarray(out["y"]).shape == (self.N, 4)
        return model, mark

    def test_one_tree_a_transform_with_parents_and_attrs(self):
        model, mark = self._transform()
        roots = tracer.recent(name="tpu_model.transform", since=mark)
        assert len(roots) == 1
        root = roots[0]
        kids = _children(root, mark)
        names = [s.name for s in kids]
        mb = math.ceil(self.N / self.BS)
        assert names.count("tpu_model.prep") == 1
        assert names.count("tpu_model.collect") == 1
        for what in ("stage", "put", "launch", "drain"):
            spans = [s for s in kids if s.name == f"tpu_model.{what}"]
            assert [s.attrs["minibatch"] for s in spans] == list(range(mb))
        assert len(kids) == 2 + 4 * mb
        # nothing else of the stretch hangs anywhere else
        assert all(s.trace_id == root.trace_id for s in kids)
        assert root.attrs["rows"] == self.N
        assert root.attrs["minibatches"] == mb
        assert root.attrs["padded_rows"] == mb * self.BS
        put = [s.attrs["bytes"] for s in kids if s.name == "tpu_model.put"]
        assert put == [self.BS * 6 * 4] * mb       # the tail goes padded
        assert root.attrs["bytes_in"] == sum(put)
        drained = [s.attrs["bytes"] for s in kids
                   if s.name == "tpu_model.drain"]
        assert drained == [self.BS * 4 * 4] * mb
        assert root.attrs["bytes_out"] == sum(drained)
        # the drain of minibatch k runs after the launch of k+1
        by = {(s.name, s.attrs.get("minibatch")): s for s in kids}
        assert by[("tpu_model.drain", 0)].start_ns >= \
            by[("tpu_model.launch", 1)].end_ns
        # children lie inside the root and do not overlap
        ordered = sorted(kids, key=lambda s: s.start_ns)
        assert root.start_ns <= ordered[0].start_ns
        assert ordered[-1].end_ns <= root.end_ns
        for a, b in zip(ordered, ordered[1:]):
            assert a.end_ns <= b.start_ns

    def test_root_nests_under_the_stage_span(self):
        _, mark = self._transform()
        root = tracer.recent(name="tpu_model.transform", since=mark)[0]
        stage = tracer.recent(name="TPUModel.transform", since=mark)[0]
        assert root.parent_id == stage.span_id

    def test_last_stats_are_the_spans_sums(self):
        model, mark = self._transform()
        root = tracer.recent(name="tpu_model.transform", since=mark)[0]
        kids = _children(root, mark)

        def ms(*names):
            return 1e3 * sum(s.seconds for s in kids if s.name in names)

        stats = model.last_stats
        assert set(stats) == {"prep_ms", "dispatch_ms", "drain_ms",
                              "total_ms"}
        assert stats["prep_ms"] == pytest.approx(
            ms("tpu_model.prep"), abs=1e-3)
        assert stats["dispatch_ms"] == pytest.approx(
            ms("tpu_model.stage", "tpu_model.put", "tpu_model.launch"),
            abs=1e-3)
        assert stats["drain_ms"] == pytest.approx(
            ms("tpu_model.drain"), abs=1e-3)
        assert stats["total_ms"] == pytest.approx(
            1e3 * root.seconds, abs=1e-3)


# ------------------------------------ the transform's host buffers (ISSUE 27)
def _plain_columns(model, x, bs, fetch, flatten=True):
    """What the parent commit's transform made, written out plainly: the
    jitted apply a zero-padded minibatch at a time, the real rows of each
    concatenated, flattened and cast."""
    module, variables = model
    run = jax.jit(lambda b: module.apply(variables, b, False))
    chunks = {e: [] for e in fetch}
    for start in range(0, len(x), bs):
        piece = x[start:start + bs]
        real = len(piece)
        pad = np.zeros((bs - real,) + piece.shape[1:], piece.dtype)
        out = run(np.concatenate([piece, pad]))
        for e in fetch:
            chunks[e].append(np.asarray(out[e])[:real])
    cols = {}
    for e, col in fetch.items():
        val = np.concatenate(chunks[e])
        if flatten and val.ndim > 2:
            val = val.reshape(val.shape[0], -1)
        cols[col] = val.astype(np.float32)
    return cols


def _rows(n, scale=1.0, seed=0):
    rng = np.random.default_rng(seed)
    return (scale * rng.normal(size=(n, 6))).astype(np.float32)


class TestTPUModelBuffers:
    BS = 8

    def _model(self, fetch=None, **kw):
        from mmlspark_tpu.dl.model import TPUModel
        return TPUModel(model=_batch_net(), minibatchSize=self.BS,
                        inputCol="x", outputCol="y", fetchDict=fetch, **kw)

    def _run(self, model, x):
        from mmlspark_tpu.core import DataFrame
        mark = now_ns()
        out = model.transform(DataFrame({"x": x}))
        root = tracer.recent(name="tpu_model.transform", since=mark)[-1]
        return out, root

    @pytest.mark.parametrize("n", [16, 20, 5],
                             ids=["multiple", "tail", "under_one"])
    @pytest.mark.parametrize("fetch,flatten,shape", [
        (None, True, (4,)),
        ({"pooled": "p", "maps": "m"}, True, (4,)),
        ({"maps": "m"}, False, (2, 2, 1)),
        ({"half": "h"}, True, (4,)),
        ({"count": "c", "shifted": "s"}, True, ()),
    ], ids=["one", "two", "four_d_kept", "bfloat16", "int32_1d"])
    def test_columns_equal_the_plain_per_minibatch_apply(
            self, n, fetch, flatten, shape):
        model = self._model(fetch, convertOutputToDenseVector=flatten)
        x = _rows(n)
        out, root = self._run(model, x)
        want = _plain_columns(model.get("model"), x, self.BS,
                              fetch or {"pooled": "y"}, flatten)
        for col, ref in want.items():
            got = out[col]
            assert got.dtype == np.float32 and got.shape == ref.shape
            np.testing.assert_array_equal(got, ref)
        first = next(iter(want.values()))
        assert first.shape == (n,) + shape
        assert root.attrs["collect_copied_bytes"] == 0

    def test_each_transform_returns_its_own_array(self):
        model = self._model()
        a, b = _rows(20, seed=1), _rows(20, seed=2)
        first = self._run(model, a)[0]["y"]
        kept = first.copy()
        second = self._run(model, b)[0]["y"]
        assert not np.shares_memory(first, second)
        np.testing.assert_array_equal(first, kept)
        assert not np.array_equal(first, second)

    def test_root_attrs_say_which_tail_buffer_served(self):
        model = self._model()
        said = [self._run(model, _rows(n))[1].attrs for n in (20, 21, 16, 3)]
        assert [a["tail_buffer"] for a in said] == \
            ["new", "held", "none", "held"]
        assert [a["collect_copied_bytes"] for a in said] == [0] * 4

    def test_no_rows_raise_as_before(self):
        with pytest.raises(ValueError):
            self._run(self._model(), np.zeros((0, 6), np.float32))

    def test_a_shorter_tail_after_a_longer_reads_zero_padding(self):
        """``shifted`` adds the padded minibatch's max: rows a longer
        tail left in the held buffer would show in a shorter one's."""
        fetch = {"shifted": "s"}
        used = self._model(fetch)
        self._run(used, np.abs(_rows(7, scale=100.0)) + 50)
        short = -np.abs(_rows(3, seed=3)) - 1       # max over the batch: 0
        got, root = self._run(used, short)
        assert root.attrs["tail_buffer"] == "held"
        fresh = self._run(self._model(fetch), short)[0]
        np.testing.assert_array_equal(got["s"], fresh["s"])
        np.testing.assert_array_equal(
            got["s"], _plain_columns(used.get("model"), short, self.BS,
                                     fetch)["s"])
        buf, dirty = used._tail_buffer
        assert dirty == 3 and not buf[3:].any()

    @pytest.mark.parametrize("change", ["dtype", "minibatch"])
    def test_another_minibatch_shape_or_dtype_drops_the_buffer(self, change):
        model = self._model()
        self._run(model, _rows(5))
        held = model._tail_buffer[0]
        x = _rows(5, scale=3.0)
        if change == "dtype":
            x = np.abs(x).astype(np.uint8)
        else:
            model.set("minibatchSize", 4)
        out, root = self._run(model, x)
        assert root.attrs["tail_buffer"] == "new"
        assert model._tail_buffer[0] is not held
        assert model._tail_buffer[0].dtype == x.dtype
        np.testing.assert_array_equal(
            out["y"], _plain_columns(model.get("model"), x,
                                     model.get("minibatchSize"),
                                     {"pooled": "y"})["y"])

    def test_an_error_hands_no_buffer_back(self):
        model = self._model()
        self._run(model, _rows(5))
        assert model._tail_buffer is not None
        model.set("inputShape", (1, 2, 3))          # Dense(4) was built for 6
        with pytest.raises(Exception):
            self._run(model, _rows(5))
        assert model._tail_buffer is None

    def test_a_copy_does_not_share_the_buffer(self):
        model = self._model()
        self._run(model, _rows(5))
        assert model._tail_buffer is not None
        assert model.copy()._tail_buffer is None

    def test_threads_on_one_model_agree_with_the_serial_result(self):
        import sys
        import threading
        model = self._model({"shifted": "s"})
        inputs = [_rows(3 + i % 6, scale=1.0 + i, seed=i) for i in range(12)]
        serial = [self._run(model, x)[0]["s"] for x in inputs]
        got = [None] * len(inputs)
        errors = []

        def work(i):
            try:
                from mmlspark_tpu.core import DataFrame
                for _ in range(5):
                    got[i] = model.transform(DataFrame({"x": inputs[i]}))["s"]
            except BaseException as e:              # read in the test below
                errors.append(e)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(len(inputs))]
        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(was)
        assert not any(t.is_alive() for t in threads)
        assert errors == []
        for want, have in zip(serial, got):
            np.testing.assert_array_equal(have, want)

    def test_a_loaded_model_transforms(self, tmp_path):
        from mmlspark_tpu.core.serialize import load_stage
        model = self._model()
        x = _rows(13)
        before = self._run(model, x)[0]["y"]
        model.save(str(tmp_path / "m"))
        loaded = load_stage(str(tmp_path / "m"))
        assert "_tail_buffer" not in loaded.__dict__   # __new__, no __init__
        for said in ("new", "held"):
            out, root = self._run(loaded, x)
            assert root.attrs["tail_buffer"] == said
            np.testing.assert_array_equal(out["y"], before)


class TestTrainEpochSpans:
    def _epoch(self, n):
        import jax.numpy as jnp
        from mmlspark_tpu.dl.train import train_epoch

        def step(state, x, y):
            return state + 1, jnp.mean(x) + jnp.sum(y)

        batches = [(np.full((4, 3), i, np.float32),
                    np.zeros((4,), np.int32)) for i in range(n)]
        mark = now_ns()
        state, losses = train_epoch(step, 0, batches)
        return state, losses, mark

    def test_n_puts_n_launches_one_fetch(self):
        n = 5
        state, losses, mark = self._epoch(n)
        assert state == n and losses == [float(i) for i in range(n)]
        root, = tracer.recent(name="train.epoch", since=mark)
        kids = _children(root, mark)
        names = [s.name for s in kids]
        assert names.count("train.put") == n
        assert names.count("train.launch") == n
        assert names.count("train.fetch") == 1
        assert len(kids) == 2 * n + 1
        assert names[0] == "train.put" and names[-1] == "train.fetch"
        for what in ("train.put", "train.launch"):
            assert [s.attrs["step"] for s in kids if s.name == what] \
                == list(range(n))
        assert root.attrs == {"steps": n, "bytes_per_step": 4 * 3 * 4 + 4 * 4}
        assert all(s.attrs["bytes"] == root.attrs["bytes_per_step"]
                   for s in kids if s.name == "train.put")

    def test_no_batches_leaves_a_bare_root(self):
        state, losses, mark = self._epoch(0)
        assert state == 0 and losses == []
        root, = tracer.recent(name="train.epoch", since=mark)
        assert root.attrs["steps"] == 0
        assert _children(root, mark) == []


# ------------------------------------------- the capture, by hand
def _span(name, start, end, sid, parent=None, **attrs):
    return Span(name=name, trace_id="t", span_id=sid, parent_id=parent,
                attrs=attrs, start_ns=start, end_ns=end,
                seconds=(end - start) / 1e9)


OFFSET = 1_000_000          # host = device + OFFSET


def _capture():
    """Two operations of two minibatches each, pipelined as ``TPUModel``
    does; device clock = host clock - OFFSET. Host times in ns:

    op A 1000-9000: launch0 1100-1200 (program 1300-3000), launch1
    1400-1500 (program 3400-5000), drain0 1500-3100, drain1 3100-5100,
    collect 5100-9000. op B 10000-18000, the same shifted by 9000."""
    spans, programs = [], []
    for n, base in enumerate((0, 9000)):
        rid = f"r{n}"
        spans += [
            _span("op", base + 1000, base + 9000, rid),
            _span("m.launch", base + 1100, base + 1200, f"l0{n}", rid,
                  minibatch=0),
            _span("m.launch", base + 1400, base + 1500, f"l1{n}", rid,
                  minibatch=1),
            _span("m.drain", base + 1500, base + 3100, f"d0{n}", rid,
                  minibatch=0),
            _span("m.drain", base + 3100, base + 5100, f"d1{n}", rid,
                  minibatch=1),
            _span("m.collect", base + 5100, base + 9000, f"c{n}", rid),
        ]
        programs += [("jit_run", base + 1300 - OFFSET, base + 3000 - OFFSET),
                     ("jit_run", base + 3400 - OFFSET, base + 5000 - OFFSET)]
    busy = [(s, e) for _, s, e in programs]
    return programs, busy, spans


def _pings(first=(500, 520, 700, 760), last=(19_000, 19_030, 19_200, 19_290)):
    """``profile_trace``'s two pings around ``_capture``'s stretch, host
    times in ns: launch ``a``-, program ``b``-``c``, fetch -``d``. The
    program began 20 (30) ns after the launch call did and its result
    was on the host 60 (90) ns after it ended."""
    spans, programs = [], []
    for n, (a, b, c, d) in enumerate((first, last)):
        spans += [_span("profile.ping", a - 10, d + 10, f"p{n}"),
                  _span("profile.ping.launch", a, a + 40, f"pl{n}", f"p{n}"),
                  _span("profile.ping.fetch", a + 50, d, f"pf{n}", f"p{n}")]
        programs.append((timeline.PING_PROGRAM + "(1)", b - OFFSET,
                         c - OFFSET))
    return programs, spans


class TestClockOffset:
    def test_recovers_a_known_offset_inside_its_interval(self):
        got = timeline.clock_offset(*_pings())
        # the tighter launch is the first ping's (20 ns), the tighter
        # result too (60 ns): the interval is the pings' intersection
        assert got["lo_ns"] == OFFSET - 20
        assert got["hi_ns"] == OFFSET + 60
        assert got["width_ns"] == 80
        assert got["lo_ns"] <= OFFSET <= got["hi_ns"]
        assert got["zero_inside"] is False
        assert got["offset_ns"] == OFFSET + 20
        assert got["by_ping"] == [[OFFSET - 20, OFFSET + 60],
                                  [OFFSET - 30, OFFSET + 90]]

    def test_zero_is_used_when_it_lies_inside(self):
        programs, spans = _pings()
        on_host = [(n, s + OFFSET, e + OFFSET) for n, s, e in programs]
        got = timeline.clock_offset(on_host, spans)
        assert (got["lo_ns"], got["hi_ns"]) == (-20, 60)
        assert got["zero_inside"] is True and got["offset_ns"] == 0

    def test_a_drifting_clock_is_bounded_by_both_pings(self):
        # by the second ping the device clock has fallen 50 ns behind
        programs, spans = _pings()
        programs[1] = (programs[1][0], programs[1][1] - 50,
                       programs[1][2] - 50)
        got = timeline.clock_offset(programs, spans)
        assert (got["lo_ns"], got["hi_ns"]) == (OFFSET + 20, OFFSET + 60)

    def test_the_stretchs_own_programs_and_spans_take_no_part(self):
        """A block that runs a device program outside any ``launch``
        span, or no span at all, is aligned like any other."""
        programs, busy, spans = _capture()
        ping_programs, ping_spans = _pings()
        for stretch_spans in (spans, []):
            capture = timeline.Capture("unused")
            capture.spans, capture.pings = stretch_spans, ping_spans
            capture.stretch = (800, 18_900)
            capture._device = (
                sorted(ping_programs + programs + [("jit_fit", 1, 2)],
                       key=lambda p: p[1]),
                timeline.merge(busy))
            assert capture.clock_offset()["width_ns"] == 80
            idle = capture.idle_by_span()["idle"]
            assert sum(row[1] for row in idle) == pytest.approx(
                (18_100 - 2 * (1700 + 1600)) * 1e-9)

    def test_raises_on_a_count_mismatch(self):
        programs, spans = _pings()
        with pytest.raises(ValueError, match="1 ping program"):
            timeline.clock_offset(programs[:1], spans)
        with pytest.raises(ValueError, match="2 launch and 1 fetch"):
            timeline.clock_offset(programs, spans[:-1])
        with pytest.raises(ValueError, match="0 ping program"):
            timeline.clock_offset([], [])

    def test_raises_on_an_empty_interval(self):
        programs, spans = _pings()
        # a program that ends after its result was on the host
        programs[1] = (programs[1][0], programs[1][1], programs[1][2] + 5000)
        with pytest.raises(ValueError, match="empty offset interval"):
            timeline.clock_offset(programs, spans)


class TestIdleBySpan:
    def _table(self, width=300, stretch=(0, 20000)):
        programs, busy, spans = _capture()
        offset = {"offset_ns": OFFSET, "width_ns": width}
        return timeline.idle_by_span(busy, spans, offset, stretch)

    def test_a_gap_is_split_across_two_spans_by_overlap(self):
        idle = {n: (s, k) for n, s, k in self._table()["idle"]}
        # gaps of 400 ns between the two programs of an operation
        # (3000-3400, 12000-12400): 100 ns under drain 0, 300 under drain
        # 1; and 100 ns of drain 1 after each operation's last program
        assert idle["m.drain"] == (pytest.approx(1000e-9), 6)

    def test_names_no_span_between_operations_and_the_spans(self):
        table = self._table()
        idle = {n: (s, k) for n, s, k in table["idle"]}
        # stretch start to the first program, 0-1300: 1000 before any
        # span, 100 under the op itself, 100 under launch 0, 100 under op
        # last program to the stretch's end, 14000-20000: 100 drain 1,
        # 3900 collect, 2000 after the last span
        assert idle["(no span)"] == (pytest.approx(3000e-9), 2)
        # 5000-10300 between the operations: 100 drain 1, 3900 collect,
        # 1000 between the trees, 100 op, 100 launch, 100 op
        assert idle["(between operations)"] == (pytest.approx(1000e-9), 1)
        assert idle["m.collect"] == (pytest.approx(7800e-9), 2)
        assert idle["m.launch"] == (pytest.approx(200e-9), 2)
        assert idle["op"] == (pytest.approx(400e-9), 4)
        assert "(unresolved)" not in idle
        assert sum(s for s, _ in idle.values()) == pytest.approx(
            (20000 - 2 * (1700 + 1600)) * 1e-9)
        assert [row[1] for row in table["idle"]] == sorted(
            (row[1] for row in table["idle"]), reverse=True)

    def test_a_gap_shorter_than_the_offset_interval_is_unresolved(self):
        idle = {n: (s, k) for n, s, k in self._table(width=500)["idle"]}
        assert idle["(unresolved)"] == (pytest.approx(800e-9), 2)
        assert idle["m.drain"] == (pytest.approx(200e-9), 2)

    def test_busy_time_is_split_the_same_way(self):
        busy = {n: (s, k) for n, s, k in self._table()["busy"]}
        # programs 1300-3000 and 3400-5000, twice: the op 100
        # (1300-1400), launch 1 100, drain 0 1500; drain 1 1600
        assert busy["m.drain"] == (pytest.approx(2 * 3100e-9), 4)
        assert busy["m.launch"] == (pytest.approx(200e-9), 2)
        assert busy["op"] == (pytest.approx(200e-9), 2)
        assert sum(s for s, _ in busy.values()) == pytest.approx(
            2 * (1700 + 1600) * 1e-9)

    def test_a_quiet_device_is_one_gap(self):
        _, _, spans = _capture()
        got = timeline.idle_by_span(
            [], spans, {"offset_ns": 0, "width_ns": 0}, (0, 20000))
        assert sum(row[1] for row in got["idle"]) == pytest.approx(20000e-9)
        assert got["busy"] == []


class TestProfileTrace:
    def test_handle_has_the_path_and_the_spans_of_the_stretch(self):
        import jax
        import jax.numpy as jnp
        from mmlspark_tpu.obs.profile import profile_trace

        fn = jax.jit(lambda x: (x @ x).sum())
        float(fn(jnp.ones((8, 8))))
        with tracer.span("before"):
            pass
        with tempfile.TemporaryDirectory() as d:
            with tracer.span("around"):
                with profile_trace(d) as capture:
                    with tracer.span("x.op"):
                        with tracer.span("x.launch"):
                            y = fn(jnp.ones((8, 8)))
                        with tracer.span("x.fetch"):
                            float(y)
            assert capture.path.endswith(".xplane.pb")
            # a CPU capture has no device plane: nothing to align, said so
            assert capture.device_programs() == ([], [])
            with pytest.raises(ValueError, match="0 ping program"):
                capture.clock_offset()
        assert [s.name for s in capture.spans] == \
            ["x.launch", "x.fetch", "x.op"]
        # one ping before the stretch and one after, for the clocks
        assert [s.name for s in capture.pings] == [
            "profile.ping.launch", "profile.ping.fetch", "profile.ping"] * 2
        assert capture.pings[2].end_ns <= capture.stretch[0]
        assert capture.stretch[1] <= capture.pings[3].start_ns
        assert capture.stretch[0] <= capture.spans[0].start_ns
        assert capture.spans[-1].end_ns <= capture.stretch[1]

    def test_the_ping_is_compiled_once_and_named_as_the_capture_reads_it(
            self):
        from mmlspark_tpu.obs import profile
        fn, x = profile._pinger()
        assert profile._pinger() == (fn, x)
        assert f"@{timeline.PING_PROGRAM} " in fn.lower(x).as_text()

    def test_both_capture_paths_start_the_profiler_device_only(
            self, monkeypatch):
        import jax
        from mmlspark_tpu.obs import xprof as xprof_mod
        from mmlspark_tpu.obs.profile import profile_trace

        seen = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, **kw: seen.append(kw["profiler_options"]))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        with tempfile.TemporaryDirectory() as d:
            with profile_trace(d):
                pass
            with profile_trace(d, host_tracer_level=2):
                pass
            xprof_mod.XprofCaptures(root=d).capture(1.0)
        assert [(o.host_tracer_level, o.python_tracer_level)
                for o in seen] == [(0, 0), (2, 0), (0, 0)]
