"""A value the runner measured at the client beside its end-to-end
metrics, in the traced run: a tail that is recorded for the ledger but
carries no bound. args: {"key": name in the runner's end_to_end}."""


def read(args, sources):
    return sources['measured'].get(args['key'])
