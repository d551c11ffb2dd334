"""Text model layers: the tile pairs a WINDOW layer's forward flash kernel
visits, as a share of the causal sweep's (every tile pair up to the
diagonal), over the measured window: `flash.window_tiles_swept` over
`flash.tiles_causal`, values of the compiled step which the program records
beside each dispatch (`observability.step_counters`), summed over the
window's steps. The full layers' share on the same rows is
`flash.tiles_swept` over the same: a window layer that read as much would
not be taking its bound from the window. A program that keeps no such
record, or counts no window layer, reports nothing."""
from harness import program

obs = program.enable()


def read(ctx):
    counters = getattr(obs, 'step_counters', None)
    if counters is None:
        return None
    counters.drain(wait=True)       # after the window: the values are there
    steps = [a for a in (ev.get('args') or {}
                         for ev in program.records(ctx, counters.SPAN))
             if 'flash.window_tiles_swept' in a and a.get('flash.tiles_causal')]
    if not steps:
        return None
    return 100.0 * sum(a['flash.window_tiles_swept'] for a in steps) \
        / sum(a['flash.tiles_causal'] for a in steps)
