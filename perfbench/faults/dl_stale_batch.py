"""A step that returns its state unchanged: every fourth batch is the one
before it, handed out again."""

from . import patch_next


def plant(ctx) -> None:
    patch_next(lambda b, st: st["prev"]
               if st["n"] % 4 == 0 and st["prev"] is not None else b)
