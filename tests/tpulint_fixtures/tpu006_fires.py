"""TPU006 fires: enable_x64 outside the dispatcher's scoped path."""
import jax
from jax.experimental import enable_x64  # [expect] x64 import


def sum64(values):
    with enable_x64():
        return values.sum()


def sum64_installed_spelling(values):
    with jax.enable_x64(True):  # [expect] x64 reference
        return values.sum()


def flip_global():
    jax.config.update("jax_enable_x64", True)  # [expect] global flip
