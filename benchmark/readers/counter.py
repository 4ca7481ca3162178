"""A count the runner read from the program, alone or per another."""


def read(rin, counter, per=None):
    if counter not in rin.counters:
        return None
    value = float(rin.counters[counter])
    if per is None:
        return value
    if not rin.counters.get(per):
        return None
    return value / float(rin.counters[per])
