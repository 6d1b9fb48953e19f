"""A forward's device milliseconds by part of the model: the device time of
the operations inside the cell's decode-side module (``decode_steps``, or
``denoise_steps`` where ``block_length`` > 0) whose stats carry
``model.<part>`` (``jax.named_scope`` in ``models/llama.py``; the suffix is
one of its ``MODEL_SCOPES``), over that module's calls.
``decode_scope_ms.unscoped`` is the module's busy time under no part, so a
cell's entries sum to the mean device busy time of a call (one forward: a
dispatch fuses one step in every cell). ``chipbench/scope_times.py`` has how
an event is put to a part. None for a program without the scopes."""

from chipbench import scope_times


def read(run, part):
    return scope_times.part_ms(run, "decode", part)
