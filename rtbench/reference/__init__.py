"""Plain references that decide ``correct``.

A configuration file names its reference (``"reference": "<name>"``),
and the harness loads ``reference/<name>.py`` by that name.  A reference
is straight ``jax.numpy`` in float32 at the highest precision and
imports nothing of the program.  It must export:

  * ``Spec.from_config(config) -> Spec``: the sizes and conventions it
    computes, read from the configuration file; a file that states what
    it does not compute raises ``ValueError``.
  * ``score(params, tokens, targets, *, spec, first, rows, quant=None)``
    -> ``(best, at_target, argmax)``, each ``(rows,)``: at positions
    ``first .. first + rows - 1`` of one sequence ``tokens``, the largest
    logit, the logit of ``targets`` and the argmax.  ``quant="fp8"`` is
    the control, the same pass one precision below the served one.
  * ``SCALES``: every leaf name of the program's parameter tree that it
    interprets, with the standard deviation ``weights.make`` draws it
    with; ``None`` for the embedding, scaled by ``EMBED_SCALE /
    sqrt(d_model)`` (then ``EMBED_SCALE`` is exported too).  A leaf that
    the table does not name stops the run.

It may export the useful work of a run, in place of ``flops.py``'s
dense GQA counts:

  * ``prefill_work(config, seqs)`` over ``(prompt_len, cached)`` and
    ``decode_work(config, seqs)`` over ``(prompt_len, tokens_out)``,
    each giving a dict of ``flops.Work`` by name.  It holds its launch
    kind's entry (``prefill``, ``decode``: the whole launch's useful
    FLOPs, which ``mfu.*`` read, and the kernel's operations and bytes)
    and may add entries for other kernels, which their own readers pass
    to ``device.roofline(run, part, kernel, work=<entry>)``.
"""
