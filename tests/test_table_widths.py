"""``table_width_cases.py`` on identical layers over a pool of keys and
values, whose decode step reads each row's own pages
(``ops/paged_kv_attention.py``): ONE decode program, at the whole table,
under a one-device mesh too, and a prefill chunk the three widths (the
hybrid and the latent family have a file each beside this one, so that
``--dist loadfile`` spreads their engines over workers; the gathered
decode ladder is the hybrid's there and the block family's in
``test_sdar.py``), and what holds for a table whatever the family: the
widths themselves."""

import pytest

FAMILY = "paged"

from table_width_cases import (  # noqa: E402,F401 — collected here
    pytest_generate_tests,
    pressed,
    served,
    test_answers_do_not_depend_on_the_rung,
    test_building_the_programs_leaves_the_key_and_the_caches,
    test_counters_say_what_the_steps_read,
    test_every_chunk_has_the_narrowest_width_that_holds_its_table,
    test_a_fresh_pool_meets_the_programs_every_later_pool_meets,
    test_every_step_has_the_narrowest_width_that_holds_its_rows,
    test_no_program_is_built_after_the_constructor,
    test_preempting_the_longest_row_lets_the_width_fall,
    test_the_constructor_compiles_each_width_once,
)


@pytest.mark.parametrize("blocks, widths", [
    (128, (32, 64, 128)), (256, (64, 128, 256)), (16, (4, 8, 16)),
    (4, (1, 2, 4)), (5, (2, 3, 5)), (7, (2, 4, 7)), (3, (3,)), (1, (1,))])
def test_the_widths_are_a_quarter_a_half_and_the_whole(blocks, widths):
    from ray_tpu.serve.llm_engine.engine import table_widths

    assert table_widths(blocks) == widths
    assert list(widths) == sorted(set(widths)) and widths[-1] == blocks
