"""Edge cases for the loop-aware HLO collective parser (analysis/hlo_parse):
nested while loops multiply trip counts, ``.clone``-suffixed computation
names resolve, and async ``-start``/``-done`` collective pairs are counted
exactly once (the ``-done`` half is a wait, not a second transfer)."""
from repro.analysis.hlo_parse import (collective_bytes_loop_aware,
                                      computation_multipliers,
                                      split_computations, trip_count)


def _hlo(*comps):
    return "\n\n".join(comps)


def test_nested_while_multiplies_trip_counts():
    txt = _hlo(
        "%inner_cond (s: s32[]) -> pred[] {\n"
        "  %bound = s32[] constant(8)\n"
        "  ROOT %lt = pred[] compare(%s, %bound), direction=LT\n"
        "}",
        "%inner_body (s: f32[128]) -> f32[128] {\n"
        "  ROOT %ar = f32[128]{0} all-reduce(%s), to_apply=%add\n"
        "}",
        "%outer_cond (s: s32[]) -> pred[] {\n"
        "  %bound = s32[] constant(4)\n"
        "  ROOT %lt = pred[] compare(%s, %bound), direction=LT\n"
        "}",
        "%outer_body (s: f32[128]) -> f32[128] {\n"
        "  ROOT %w = f32[128] while(%s), condition=%inner_cond, "
        "body=%inner_body\n"
        "}",
        "ENTRY %main (p0: f32[128]) -> f32[128] {\n"
        "  ROOT %w = f32[128] while(%p0), condition=%outer_cond, "
        "body=%outer_body\n"
        "}",
    )
    mults = computation_multipliers(txt)
    assert mults["outer_body"] == 4.0
    assert mults["inner_body"] == 4.0 * 8.0
    rep = collective_bytes_loop_aware(txt)
    # one f32[128] all-reduce (512 B) per inner iteration, 4*8 iterations
    assert rep["all-reduce"] == 4 * 8 * 512
    assert rep["all-reduce_count"] == 32.0


def test_clone_suffixed_computations_resolve():
    # post-optimization HLO duplicates computations under ``.clone``
    # suffixes; the while reference and the definition must still match
    txt = _hlo(
        "%cond.clone (s: s32[]) -> pred[] {\n"
        "  %bound = s32[] constant(3)\n"
        "  ROOT %lt = pred[] compare(%s, %bound), direction=LT\n"
        "}",
        "%body.clone (s: f32[64]) -> f32[64] {\n"
        "  ROOT %ag = f32[256]{0} all-gather(%s), dimensions={0}\n"
        "}",
        "ENTRY %main (p0: f32[64]) -> f32[64] {\n"
        "  ROOT %w = f32[64] while(%p0), condition=%cond.clone, "
        "body=%body.clone\n"
        "}",
    )
    comps = split_computations(txt)
    assert "body.clone" in comps and "cond.clone" in comps
    mults = computation_multipliers(txt)
    assert mults["body.clone"] == 3.0
    rep = collective_bytes_loop_aware(txt)
    assert rep["all-gather"] == 3 * 256 * 4
    assert rep["all-gather_count"] == 3.0


def test_async_start_done_pair_counted_once():
    txt = _hlo(
        "ENTRY %main (p0: f32[64]) -> f32[256] {\n"
        "  %ags = f32[256]{0} all-gather-start(%p0), dimensions={0}\n"
        "  ROOT %agd = f32[256]{0} all-gather-done(%ags)\n"
        "}",
    )
    rep = collective_bytes_loop_aware(txt)
    # the -start leg carries the bytes; the -done leg is a wait
    assert rep["all-gather"] == 256 * 4
    assert rep["all-gather_count"] == 1.0


def test_unreachable_computation_contributes_nothing():
    txt = _hlo(
        "%orphan (s: f32[64]) -> f32[64] {\n"
        "  ROOT %ar = f32[64]{0} all-reduce(%s), to_apply=%add\n"
        "}",
        "ENTRY %main (p0: f32[64]) -> f32[64] {\n"
        "  ROOT %t = f32[64] copy(%p0)\n"
        "}",
    )
    rep = collective_bytes_loop_aware(txt)
    assert rep["all-reduce"] == 0.0
    assert rep["all-reduce_count"] == 0.0


def test_trip_count_defaults_to_one_without_constant():
    assert trip_count("ROOT %lt = pred[] compare(%a, %b), direction=LT") == 1
