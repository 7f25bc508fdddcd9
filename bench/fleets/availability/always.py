"""Every client always available."""


def availability(spec, C, seed, dt):
    return None
