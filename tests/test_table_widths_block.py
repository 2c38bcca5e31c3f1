"""``table_width_cases.py`` on diffusion over blocks of 4 positions
(``model._blockwise(4)``: the paged family's layers and pool, a pass of
4 query rows a row under the block's horizon). Its decode step GATHERS,
so it keeps the ladder of three decode programs that the paged family,
which reads by row since PR 58, no longer has: the witness of the ladder
SDAR and Phi still run, under a one-device mesh too. A file of its own
so that ``--dist loadfile`` gives this family's engines a worker of
their own."""

FAMILY = "block"

from table_width_cases import (  # noqa: E402,F401 — collected here
    pytest_generate_tests,
    pressed,
    served,
    test_a_fresh_pool_meets_the_programs_every_later_pool_meets,
    test_answers_do_not_depend_on_the_rung,
    test_building_the_programs_leaves_the_key_and_the_caches,
    test_counters_say_what_the_steps_read,
    test_every_chunk_has_the_narrowest_width_that_holds_its_table,
    test_every_step_has_the_narrowest_width_that_holds_its_rows,
    test_no_program_is_built_after_the_constructor,
    test_preempting_the_longest_row_lets_the_width_fall,
    test_the_constructor_compiles_each_width_once,
)
