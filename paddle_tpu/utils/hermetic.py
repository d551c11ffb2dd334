"""The zero-egress gate of the hermetic build."""
import os


def allow_egress(base_env=None):
    """True when this process may attempt network fetches.

    The build is hermetic (zero-egress) BY DEFAULT: TPU pods and the test
    harness run without internet, so code that could fetch (utils/download)
    must check this gate and fall back to pre-seeded caches when it is off.
    Opt in with PADDLE_TPU_ALLOW_EGRESS=1.
    """
    env = os.environ if base_env is None else base_env
    return str(env.get('PADDLE_TPU_ALLOW_EGRESS', '')).lower() in (
        '1', 'true', 'yes', 'on')
