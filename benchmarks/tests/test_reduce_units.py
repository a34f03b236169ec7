"""The trace reduction's arithmetic on hand-made intervals."""

import trace_reduce as tr


def test_union_merges_overlaps_and_keeps_gaps():
    merged = tr.union([(0, 10, "a"), (5, 12, "b"), (20, 30, "c")])
    assert merged == [(0, 12), (20, 30)]
    assert tr.length(merged) == 22


def test_clip_cuts_to_the_window():
    assert tr.clip([(0, 10, "a"), (20, 30, "b")], 5, 25) \
        == [(5, 10, "a"), (20, 25, "b")]


def test_self_times_take_nested_operations_out_of_their_holder():
    events = [(0, 100, "while"), (10, 30, "fusion"), (30, 40, "copy"),
              (50, 90, "while.inner"), (60, 70, "fusion"),
              (120, 130, "fusion")]
    assert dict(tr.self_times(events)) == {
        "while": 30, "fusion": 40, "copy": 10, "while.inner": 30}
    assert sum(tr.self_times(events).values()) == tr.length(tr.union(events))


def test_collectives_exposed_and_in_flight():
    ops = [(0, 10, "fusion.1"), (10, 14, "collective-permute-start.1"),
           (14, 30, "fusion.2"), (30, 36, "collective-permute-done.1"),
           (40, 50, "all-reduce.3")]
    async_ops = [(10, 36, "collective-permute-start.1")]
    exposed, flight = tr.collective_times(ops, async_ops)
    assert (exposed, flight) == (20.0, 36.0)
    assert tr.collective_times(ops[:1], []) == (0.0, 0.0)


def test_idle_gaps_go_to_the_host_span_at_their_midpoint():
    busy = [(10, 20), (50, 60)]
    spans = [(0, 12, "round_or_chunk_boundary"), (18, 52, "host_feed")]
    gaps = dict(tr.idle_gaps(busy, 0, 100, spans))
    assert gaps == {"round_or_chunk_boundary": 10 / 1e9,
                    "host_feed": 30 / 1e9, "unattributed": 40 / 1e9}


def test_step_module_is_the_program_that_ran_once_per_step():
    modules = [(0, 5, "jit_step"), (10, 15, "jit_step"), (20, 21, "jit_eval"),
               (30, 31, "jit_eval"), (40, 49, "jit_fedavg")]
    name, mean_s = tr.step_module(modules, 2)
    assert name == "jit_step" and mean_s == 5 / 1e9
    assert tr.step_module(modules, 7) is None


def test_host_spans_label_feed_and_boundaries():
    run = {"tap_calls": [(0.0, 1.0, True), (3.0, 4.0, False),
                         (9.0, 10.0, True)]}
    assert tr.host_spans(run) == [
        (0.0, 1.0, "step_dispatch"), (1.0, 3.0, "host_feed"),
        (3.0, 4.0, "step_dispatch"),
        (4.0, 9.0, "round_or_chunk_boundary"),
        (9.0, 10.0, "step_dispatch")]
