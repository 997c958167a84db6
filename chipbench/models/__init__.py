"""Model kinds of the benchmark, one module each: ``models/<model>.py``,
found by the configuration's ``model`` name (``harness.model_kind``) as
``counts/<model>.py`` is. A kind holds everything the harness, the program
build and the plain reference need to know of one kind of model:

* ``make_data(traffic, cfg, key, seed)``: the federation's data on the
  device, ``train_x``, ``train_y``, ``test_x``, ``test_y`` with leading
  (M, rows) axes, from the seed and the traffic file;
* ``trainer_kwargs(cfg)``: what ``program.build`` passes to ``P4Trainer``
  besides ``cfg=``;
* ``param_shapes(cfg)`` and ``init_model(cfg, key)``: one model's
  parameters by key, and their initialization from a key;
* ``apply(cfg, params, x, prec)``: the reference's forward pass on a batch,
  in the dtype of ``params`` and ``x``, matmuls at ``prec``;
* ``mutual_loss(logits, other_logits, y, weight)``: the reference's loss
  (Eqs. 8-9), the other model's logits held constant;
* ``correct_counts(cfg, private, test_x, test_y)``: per client, the test
  examples the reference's personalized models get right, and
  ``run_correct(cfg, acc, test_y)``: the same count from what the
  program's ``P4Trainer.evaluate`` returns (``compare``'s ``eval_gap``);
* ``shrink(cfg, mix)``: the model's sizes in a CPU test run.

A kind imports nothing of the program under test.

What the harness holds for a kind, whatever its size: one state on the
device (the program's, then the reference's, donated from round to round;
the states a run's changes are read from live on the host), and in the
reference one block of ``reference_block`` clients stepping one block of
``reference_example_block`` examples at a time (an optional key of the
configuration; without it a client's batch is one block), besides the
temporaries of one model's size that a compiled round keeps (gradients,
noise, layout copies). ``mutual_loss`` is therefore a mean over
examples, so that block means weighted by block size give the batch mean.
``apply`` may save memory inside itself, for example by checkpointing
attention in query blocks: that is the kind's business. A federation of
one client (``clients`` 1) is valid: it forms one group and has no pair of
distances to compare.
"""
