"""The rows the busiest held expert computed over the mean of the held
experts' rows, from the program's per-expert row counters, averaged over
the window's steps and expert layers.  1 is an even load; a program
without the counters leaves nothing to read."""


def read(run):
    ratios = [max(layer) * len(layer) / sum(layer)
              for step in run.info.get("rows", ()) for layer in step
              if sum(layer)]
    return sum(ratios) / len(ratios) if ratios else None
