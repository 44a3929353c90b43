"""Tier-1 runs the benchmark's own CPU checks (``benchmark/checks/test_*.py``).

They are the only CPU rehearsal of ``benchmark/run.py --tiny`` and of
``drivers/train.py measure`` against the program: a PR that renames a span,
changes ``Engine.train_step``'s signature or breaks the recorder rings the
readers take learns it here and not on the chip. One thin module per check
file (``tests/test_benchmark_checks_<name>.py``) takes that file's names as
its own, so that pytest counts each case and ``--dist loadfile`` spreads the
files; no check's body is copied.
"""

import os
import runpy

# the checks' own path set-up (their modules do ``from harness import ...``)
runpy.run_path(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                            "benchmark", "checks", "conftest.py"))

from harness import manifest  # noqa: E402

# A case that asserts on a wall clock would be unsteady beside five other
# workers: this one (test_window_dataset.py) is sleep-paced with 30 ms of tolerance.
LEFT_OUT = {"test_ends_within_one_batch_of_the_deadline_and_cycles_epochs"}


def names_of(check):
    """The module-level names of ``benchmark/checks/<check>.py``: its cases,
    fixtures and helpers, less the cases left out above."""
    return {k: v for k, v in vars(manifest.load_module("checks", check)).items()
            if not k.startswith("__") and k not in LEFT_OUT}
