"""The on-chip benchmark of the planned CNN training step.

``run.py`` (beside this package) runs one cell of ``BENCHMARK.json`` once.
The pieces it is built from:

* ``cell``      - finds a cell's configuration, traffic, limits and metric
                  readers by the names in ``BENCHMARK.json``;
* ``measure``   - one run of one cell, and its result line;
* ``record``    - what a run measured, as the metric readers see it;
* ``inputs``    - the seeded pool of host image batches the loop is fed;
* ``loop``      - drives the program's own launcher and training loop,
                  timing every step at the loop boundary;
* ``reference`` - a plain float32 CNN with AdamW, independent of the
                  program, that decides whether the timed path is correct;
* ``compare``   - the numbers compared with it, and their limits;
* ``work``      - operations and bytes of every stage, from shapes alone;
* ``trace``     - the reduction of a profiler trace to per-layer numbers;
* ``faults``    - faults planted under the timed path, for the tests and
                  for setting the limits.
"""
