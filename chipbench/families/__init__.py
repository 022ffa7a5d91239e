"""What a family owns, and how a ``model_config`` PR adds one.

A family is the system under test for every configuration of one
architecture: ``families/<family>.py``, with the plain reference
``reference/<family>.py`` and the count of operations
``flops/<family>.py`` beside it. A configuration names its family
(``configs/<config>.json``: ``"family"``), a cell its configuration, and
``run.py``, ``calibrate.py`` and the tests under ``tests/chipbench`` find
everything else from there through :func:`load`. None of them names a
family.

Adding a family
---------------
A PR adds files and entries only, and edits no file that is there:

* ``configs/<config>.json``: the published sizes under the published
  keys, ``family``, and what ``changed`` and was ``assumed``;
* ``workloads/<config>.<traffic>.json``: the cell's parameters, ``config``,
  ``chips``, ``limits`` (``loss_gap``, ``grad_gap``, ``change_gap``, set
  from ``calibrate.py``'s readings), ``learning_rate``, ``why``, and
  ``reference_block_rows`` where the reference takes its rows in blocks;
* ``families/<family>.py``, ``reference/<family>.py`` (imports nothing of
  the program), ``flops/<family>.py``;
* a reader ``layer_metrics/<metric>.py`` for each per-layer metric it
  brings (device time of a kernel or a scope: ``kernel_s``, ``scope_s``
  and ``scoped`` of ``trace_reduce.reduce``; its roofline from
  ``Job.part_flops``);
* the entries in ``BENCHMARK.json``.

The tests then run over the new cell unasked (``tests/chipbench``, every
one parametrised over the cells of ``BENCHMARK.json``; they find the
family from the cell as ``run.py`` does and name none): a sound run is
correct; a state left unchanged, half of the batch left out, the
exchange between chips left out (mesh cells) and the bfloat16 control
are not; every fault keeps a row of the cell's batch; the cell yields
its end-to-end and its per-layer metrics; the program's outputs, its
loss, its gradients and one step of its optimizer agree with the
reference (cells without a mesh); the reference in blocks of rows is the
reference (once a family); every name resolves to a file; a family that
lacks a piece of what follows is told which (:func:`load`). The CPU
sizes they run at are the family's own (:func:`tiny` below). A second
family lives under ``tests/chipbench/toy/`` for the tests alone, laid
out as this directory's parent is: a worked example of every file.

The module
----------
``UPDATE_PROGRAM``
    The XLA module name of the optimizer's update program.

``tiny(cell, cfg) -> (cell, cfg)``
    The family's CPU sizes: every width of a configuration and every
    length of a cell shrunk, nothing else changed. Copies; the chip runs
    the files as they are.

``Job(cfg, cell, seed, ctx)``
    One cell's training job on ``ctx``, weights (made by the reference,
    on the device, in one jitted call) and batches from ``seed``.

    What a step is made of (``run.one_step`` calls them in this order,
    then ``loss.backward()`` and ``job.trainer.step(1)``):

    ``pool``            the host batches, cycled; all rows differ. A batch
                        is a dict, its arrays' rows on axis 0
                        (``calibrate.py`` cuts them there);
    ``scope()``         the context the loop runs in (a mesh, or none);
    ``upload(batch)``   host batch -> what ``forward`` and ``loss`` take;
    ``forward(dev)``    the hybridized net's outputs;
    ``loss(out, dev)``  the scalar loss, a mean over the rows. Its
                        per-row loss goes through
                        ``gluon.loss.SoftmaxCrossEntropyLoss.forward`` with
                        the rows on axis 0: the faults "half of the batch
                        left out" and "the exchange left out" are planted
                        there (``tests/chipbench/test_correct.py``
                        ``keep_rows``);
    ``net``, ``trainer``  ``net.compile_count`` and
                        ``trainer._fused_fallback_taken`` are read;
    ``timing``          {part of the build: seconds}, for the notes.

    What a batch is worth:

    ``tokens(batch)``      its real tokens;
    ``step_flops(batch)``  the FLOPs forward and backward require;
    ``part_flops(batch)``  {part: FLOPs} of the step's named parts that a
                           kernel's roofline needs; {} where none;
    ``update_bytes()``     the bytes the optimizer has to move a step.

    The readings ``correct`` rests on (``check.py``), by the program's
    names of its leaves:

    ``param_raws()``           {leaf: raw array} now;
    ``initial_raws(like)``     the seed's weights again, laid out alike;
    ``first_gradient_raws()``  ({leaf: raw}, scale): step 1's gradient as
                               the optimizer got it. A leaf the optimizer
                               holds no slot for (``grad_req='null'``) is
                               left out, here and in the reference's norms;
    ``leaf_parts()``           {leaf: n} for a leaf the reference reads as
                               n equal parts along its first axis (a fused
                               projection, experts stacked in one leaf);
    ``reference_batches(batches)``  host batches as the reference takes
                               them;
    ``follow_reference(batches, dtype='float32')``  the reference's
                               ``losses``, ``grad_norms``, ``change_norms``
                               over the same steps, by the program's names
                               (``name[j]`` for a leaf read in parts); in
                               ``bfloat16`` it is the control; its blocks
                               of rows are the cell's
                               ``reference_block_rows`` when it is called;
    ``free()``                 drop the program's state.

    The reference's side of the agreement tests
    (``tests/chipbench/test_flops_and_reference.py``), by the program's
    names and at the reference's own precision:

    ``reference_forward(batch)``  what the reference's forward gives for
                               each output ``forward`` gives, in that
                               order; None for an output it does not make;
    ``reference_loss_and_gradients(batch)``  ``(loss, {leaf: gradient})``
                               of one host batch.
"""

import importlib

MODULE = ('UPDATE_PROGRAM', 'tiny', 'Job')
JOB = ('scope', 'upload', 'forward', 'loss', 'tokens', 'step_flops',
       'part_flops', 'update_bytes', 'leaf_parts', 'param_raws',
       'first_gradient_raws', 'initial_raws', 'reference_batches',
       'follow_reference', 'free', 'reference_forward',
       'reference_loss_and_gradients')


def load(name):
    """The module of the family ``name``, held to the contract above: a
    piece that is missing is named, here and not where it is first
    called."""
    family = importlib.import_module(f'{__name__}.{name}')
    missing = [k for k in MODULE if not hasattr(family, k)]
    if hasattr(family, 'Job'):
        missing += [f'Job.{k}' for k in JOB if not hasattr(family.Job, k)]
    if missing:
        raise NotImplementedError(
            f'the family {name!r} lacks {", ".join(missing)}: '
            'chipbench/families/__init__.py says what a family owns')
    return family
