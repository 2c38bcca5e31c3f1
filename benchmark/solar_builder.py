"""``solar-open2-250b-serve-1chip``'s ``builder.path``: the program's
``ray_tpu.models.solar_open2.SolarOpen2Config`` from the file's keys,
and on a tree that lacks that module an end with NO result and another
exit code than 0.

Why a function here and not the class's dotted path. The driver tries a
new cell on the parent commit with this PR's benchmark files laid over
it, and takes "another exit code than 0, soon" for a parent that cannot
run the configuration; a parent that prints a result is compared like
any other. Since PR 54 ``harness.main`` turns an ``Exception`` or a
``SystemExit`` raised once the device is found into a result's line
(``correct`` false, no metric) and exit code 0, which is right for a
run that broke and wrong for a program that does not have the model: of
"a directory that holds the benchmark without the program" the harness
itself says that it "ends here with no result and another code than 0",
and a tree without ``models/solar_open2.py`` is that, one module
narrower (my chip run, PR 55, before this file: the parent ended in 12 s
with ``ModuleNotFoundError`` on a result's line and exit code 0). So the
module's absence, and that alone, is raised as a ``BaseException``,
which the harness lets through: a traceback on standard error, exit
code 1, nothing started that would have to be stopped (no replica, no
thread: ``serve_cell.run`` builds the configuration first)."""

from __future__ import annotations

MODULE = "ray_tpu.models.solar_open2"


class ProgramLacksTheModel(BaseException):
    """This tree's program has no ``models/solar_open2.py``."""


def config(**kwargs):
    try:
        from ray_tpu.models.solar_open2 import SolarOpen2Config
    except ModuleNotFoundError as exc:
        if exc.name != MODULE:
            raise
        raise ProgramLacksTheModel(
            f"no module {MODULE}: this program cannot build "
            "solar-open2-250b-serve-1chip (it is PR 55's)") from None
    return SolarOpen2Config(**kwargs)
