"""The on-chip benchmark of the program's planned training step.

``run.py`` (beside this package) runs one cell of ``BENCHMARK.json`` once.
The pieces it is built from:

* ``cell``      - finds a cell's configuration, traffic, limits, metric
                  readers and model family by the names in
                  ``BENCHMARK.json`` and the configuration file;
* ``measure``   - one run of one cell, and its result line;
* ``record``    - what a run measured, as the metric readers see it;
* ``inputs``    - the seeded pool of host batches the loop is fed;
* ``loop``      - drives the program's own launcher and training loop,
                  timing every step at the loop boundary;
* ``reference`` - the family's plain float32 model with AdamW, independent
                  of the program, that decides whether the timed path is
                  correct;
* ``compare``   - the numbers compared with it, and their limits;
* ``work``      - the least time of the passes the family counts from
                  shapes alone;
* ``trace``     - the reduction of a profiler trace to per-layer numbers;
* ``faults``    - faults planted under the timed path, for the tests and
                  for setting the limits.

Everything that belongs to one kind of model (its inputs, its reference,
its stages and their work) is in ``families/<family>.py`` beside this
package.
"""
