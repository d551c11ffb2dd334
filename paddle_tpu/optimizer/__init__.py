"""paddle_tpu.optimizer. Parity: python/paddle/optimizer/__init__.py."""
from .optimizer import (Optimizer, SGD, Momentum, Adam, AdamW, Adamax,
                        Adadelta, Adagrad, RMSProp, Lamb, LarsMomentum, Ftrl,
                        DecayedAdagrad, DecayedAdagradOptimizer,
                        Dpsgd, DpsgdOptimizer)
from . import lr
from .lr import *  # noqa
from .extras import (ExponentialMovingAverage, LookAhead, ModelAverage,
                     PipelineOptimizer, RecomputeOptimizer)

# -- 1.8 *Optimizer aliases + 2.0-beta *LR scheduler names -------------------
MomentumOptimizer = Momentum
AdagradOptimizer = Adagrad
AdadeltaOptimizer = Adadelta
AdamOptimizer = Adam
AdamaxOptimizer = Adamax
RMSPropOptimizer = RMSProp
FtrlOptimizer = Ftrl
LambOptimizer = Lamb
LarsMomentumOptimizer = LarsMomentum
SGDOptimizer = SGD
DGCMomentumOptimizer = Momentum   # dgc = bf16-compressed allreduce knob
LookaheadOptimizer = LookAhead
ModelAverageOptimizer = ModelAverage

from .lr import (NoamDecay as NoamLR,  # noqa: F401,E402
                 PiecewiseDecay as PiecewiseLR,
                 NaturalExpDecay as NaturalExpLR,
                 InverseTimeDecay as InverseTimeLR,
                 PolynomialDecay as PolynomialLR,
                 LinearWarmup as LinearLrWarmup,
                 ExponentialDecay as ExponentialLR,
                 MultiStepDecay as MultiStepLR,
                 StepDecay as StepLR,
                 LambdaDecay as LambdaLR,
                 ReduceOnPlateau as ReduceLROnPlateau,
                 CosineAnnealingDecay as CosineAnnealingLR)


from . import lr_scheduler  # noqa: E402,F401  (2.0-beta module path)
from .lr_scheduler import _LRScheduler  # noqa: E402,F401
