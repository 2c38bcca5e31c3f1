"""``table_width_cases.py`` on a hybrid's three caches: the one
full-attention pool is what the table's width reads, beside a ring and a
state a row. A file of its own so that ``--dist loadfile`` gives this
family's engines a worker of their own."""

FAMILY = "hybrid"

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
