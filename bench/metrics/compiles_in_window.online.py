"""Backend compiles that ended inside the measured window
(``jax.monitoring``'s backend-compile events); after the set-up has
served every rung of the padding ladder this should read 0."""


def read(r):
    return float(r.compile_log.between(*r.window))
