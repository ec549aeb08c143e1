"""Sum of some series of one labelled counter over the window, over the
sum of other counters: ``scale * numerator / denominator``. A side is
``{"name": <counter>, "where": {<label>: [<value>, ...]}}``: every series
of the counter whose labels take one of the listed values (a label left
out may take any). ``registry_delta`` keys a series ``name{k="v",...}``."""

import re

_KEY = re.compile(r'^(?P<name>[^{]+)(?:\{(?P<labels>.*)\})?$')
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _total(delta, side):
    total, found = 0.0, False
    for key, value in delta.items():
        m = _KEY.match(key)
        if m.group("name") != side["name"]:
            continue
        labels = dict(_LABEL.findall(m.group("labels") or ""))
        if all(labels.get(k) in allowed
               for k, allowed in side.get("where", {}).items()):
            total, found = total + value, True
    return total if found else None


def read(params, run):
    num = _total(run.registry_delta, params["numerator"])
    den = _total(run.registry_delta, params["denominator"])
    if num is None or not den or den <= 0:
        return None
    return params.get("scale", 1.0) * num / den
