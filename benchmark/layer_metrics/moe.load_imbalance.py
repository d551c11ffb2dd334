"""Text model layers: the expert layers' load imbalance over the measured
window: the rows of the busiest held expert over the mean rows of a held
expert (`moe.expert_rows_max` / `moe.expert_rows_mean`: values of the
compiled step, which the program records beside each dispatch without
waiting for them, `observability.step_counters`), the mean over the
window's steps. 1 is an even router. A program that keeps no such record
reports nothing."""
from harness import program

obs = program.enable()


def read(ctx):
    counters = getattr(obs, 'step_counters', None)
    if counters is None:
        return None
    counters.drain(wait=True)       # after the window: the values are there
    ratios = [a['moe.expert_rows_max'] / a['moe.expert_rows_mean']
              for a in (ev.get('args') or {}
                        for ev in program.records(ctx, counters.SPAN))
              if a.get('moe.expert_rows_mean')]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
