"""Device, across chips: time per step in which a collective runs and no
compute does, on the chip where that is longest. A cell on one chip has no
collective and reports nothing."""


def read(ctx):
    chips = [c for c in ctx['trace'].values()
             if c['steps'] and c['collective_s'] > 0]
    if not chips:
        return None
    return max(1e3 * c['exposed_collective_s'] / c['steps'] for c in chips)
