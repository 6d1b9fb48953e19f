"""A prefill dispatch's device milliseconds by part of the model: as
``decode_scope_ms`` over the calls of the modules whose name carries
``prefill`` (a dispatch computes its rows' bucket whatever the real rows
are: ``prefill_rows_mean``). The first tokens' sampler is a program of its
own (``sample_tokens_packed``) and in no entry here."""

from chipbench import scope_times


def read(run, part):
    return scope_times.part_ms(run, "prefill", part)
