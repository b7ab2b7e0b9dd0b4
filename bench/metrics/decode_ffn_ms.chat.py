"""Device milliseconds of one decode step (``jit_decode_step``) spent in
the ops of the ``ffn`` scope, the mean over the step's executions in the
trace; None where the run's record holds no per-scope split."""
import scopes


def read(rec):
    return scopes.scope_ms(rec, "jit_decode_step", "ffn")
