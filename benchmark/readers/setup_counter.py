"""A number the set-up recorded (``args.key``): compile seconds from
``jax.monitoring``'s backend-compile events, steps of warm-up and ramp."""


def read(r, args):
    return r.setup.get(args["key"])
