"""Mean duration of one of the benchmark's own host spans over the
window. args: {"span": name, "scale": multiplier, default 1}."""


def read(args, sources):
    durations = sources['spans'].get(args['span'])
    if not durations:
        return None
    return sum(durations) / len(durations) * args.get('scale', 1)
