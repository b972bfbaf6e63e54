"""Mean of a quantity the runner sampled on its own clock during the
window. args: {"gauge": name, "scale": multiplier, default 1}."""


def read(args, sources):
    values = sources['samples'].get(args['gauge'])
    if not values:
        return None
    return sum(values) / len(values) * args.get('scale', 1)
