"""Text model layers: device time per step under the multi-token-prediction
module (the scope `mtp` of `text/joyai_flash.py`: the second embedding lookup,
the two norms and `eh_proj`, the module's decoder block, its final norm and
its pass through the shared head), forward, recomputation and backward
together, on the chip where it takes longest. A program without the scope
reports nothing."""
from harness import program, scopes

program.enable()


def read(ctx):
    return scopes.read(ctx, 'mtp')
