"""One module per algorithm a traffic mix can name (its ``"algorithm"``),
found by that name (``catalog.algorithm``): a traffic mix of a new
algorithm is this directory's new file and a ``traffic/<name>.json``.

Each module gives

* ``CHECK``, the name of the number compared with the reference, whose
  limit the traffic file states under ``limits``;
* ``jobs(traffic, graph, seed)``, the warm-up jobs and the window's jobs;
* ``run(engine, job)``, one job through the program's public entry point
  (``repro.core.algorithms``), returning ``(answer, RunStats)``;
* ``Reference(graph)``, the plain numpy reference over the generated edge
  list, built once per run; ``answer(job)`` is its answer to a job;
* ``gap(got, want)``, the number compared, for one job;
* ``control(config, graph, jobs)``, the control's answers to ``jobs``: the
  reference with one guarantee of the configuration broken.

A reference imports nothing of the program.
"""
