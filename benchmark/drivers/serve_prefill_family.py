"""Driver `serve_prefill_family`: `serve_prefill`'s run for a cell
whose family the program may not read yet.

`serve_prefill` builds the program's configuration inside the
replica's constructor, and a constructor that raises is tried again by
the controller until `serve.run`'s 1,100 s are over. This driver asks
the program for the configuration first, in this process, before
anything is started: a program whose `config_from_hf` does not know the
family raises at once and the run exits non-zero. Everything else is
`serve_prefill.run`.

Traffic file: as `serve_openloop`'s.
"""

from __future__ import annotations

from benchmark.drivers import serve_prefill


def run(job: dict) -> dict:
    from ray_tpu.models import config_from_hf
    config_from_hf(job["config"], max(job["traffic"]["pad_to"]))
    return serve_prefill.run(job)
