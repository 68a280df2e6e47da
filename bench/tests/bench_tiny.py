"""Tiny sizes of each configuration, for runs of the harness on the CPU."""

import time

from bench import harness

TINY = {
    "lastfm_hetrec": dict(users=40, artists=60, user_artists_rows=240,
                          max_artists_per_user=8, friend_pairs=80),
}


def tiny_run(cell, trace=False, seconds=0.5, **kw):
    """One run of ``cell`` through the harness's internal entry (no chip)."""
    config = harness.cell_of(harness.manifest(), cell)["config"]
    return harness.run_cell(cell, 2**31 + 17, seconds, trace,
                            started=time.perf_counter(), require_tpu=False,
                            overrides=TINY[config], **kw)
