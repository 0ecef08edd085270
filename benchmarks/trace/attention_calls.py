"""The attention kernel's `tpu_custom_call`s of a cell that serves a
model with experts, by instruction name: every Pallas call whose first
operand is s32 (its traced grid bound) and that is no grouped product
of `jax.lax.ragged_dot` (`custom_calls.py` has the rule, the samples and
the walk over the profile).  In `trinity-mini-d8`'s cells those are the
grouped-query kernel's calls, one a layer, eight a step: their
instructions carry the model's `jax.named_scope`
(`%window_attention.N`, `%full_attention.N`), which tells the two kinds
of layer apart in a profile; the rule does not lean on it."""
from benchmarks.trace import custom_calls

is_attention = custom_calls.is_latent


def seconds_and_calls(obs):
    """(seconds, calls) of the attention kernel inside the traced
    window; None with no trace, no profile or no window span."""
    return custom_calls.seconds_and_calls(obs, is_attention)
