"""``table_width_cases.py`` on latent attention over a pool of one vector
a position: a decode step reads each row's own pages through the tables,
so it has ONE program, at the whole table, and a prefill chunk the three
widths. A file of its own so that ``--dist loadfile`` gives this
family's engines a worker of their own. The case under a mesh is not
imported: the tiny Xing has experts, whose counters the constructor
makes off the mesh, so under one each program is built a second time
by the first step that carries them back (at the parent commit too:
``ROADMAP.md`` D16; the benchmark's one-chip cells have no mesh)."""

FAMILY = "latent"

from table_width_cases import (  # noqa: E402,F401 — collected here
    pytest_generate_tests,
    pressed,
    served,
    test_answers_do_not_depend_on_the_rung,
    test_building_the_programs_leaves_the_key_and_the_caches,
    test_counters_say_what_the_steps_read,
    test_every_chunk_has_the_narrowest_width_that_holds_its_table,
    test_every_step_has_the_narrowest_width_that_holds_its_rows,
    test_no_program_is_built_after_the_constructor,
    test_preempting_the_longest_row_lets_the_width_fall,
    test_the_constructor_compiles_each_width_once,
)
