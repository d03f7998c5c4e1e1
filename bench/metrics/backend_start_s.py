"""Host clock around the import of JAX and the start of its TPU backend
(the first ``jax.devices()``), a part of set-up the program does not
control."""


def read(run):
    return run.backend_start_s
