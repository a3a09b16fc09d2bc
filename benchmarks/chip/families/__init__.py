"""Model families: all the benchmark knows of one kind of model.

A configuration file names its family (``"family": "cnn"``), and
``chipbench.cell.family`` imports ``families/<family>.py``.  The rest of
the harness is the same for every family.  A family module provides:

* ``check_program(config)``        - exit when the program's registry
                                     entry for ``config["arch"]`` is not the
                                     configuration the file states;
* ``launcher_args(config, traffic)`` - the family's own launcher flags;
* ``make_batch(seed, index, config, traffic)`` - one host batch, a dict of
                                     arrays whose leading axis is the
                                     examples, drawn from ``(seed, index)``;
* ``tokens(batch)``                - tokens a step trains on, or None for a
                                     model that reads no tokens;
* ``init_params(config, seed)``    - the reference's weights, the tree the
                                     program's state holds;
* ``loss_fn(params, batch, config, q)`` - the plain float32 loss, with
                                     ``q`` applied to every operand of a
                                     convolution or a matrix product (the
                                     float8 control's rounding);
* ``BLOCK_ROWS``                   - examples of one block of the
                                     reference's gradient;
* ``stages(config)``               - ``(name, kind)`` of every stage that
                                     runs under a ``jax.named_scope`` of its
                                     name in the program;
* ``STAGE_PATTERN``                - the regex that finds such a scope in an
                                     ``op_name`` (its group 1 is the stage);
* ``passes(config, traffic, batch)`` - every pass of one training step on
                                     ``batch`` examples, as ``chipbench.work.Pass``;
* ``step_flops(config, traffic, batch)`` and ``param_count(config)``.
"""
