"""A step that returns its state unchanged: inside the window, from the
second cycle on, restore hands back the step before the one asked for."""


def plant(ctx) -> None:
    from tpu3fs.ckpt import CheckpointLoader

    inner = CheckpointLoader.restore

    def restore(self, step, like=None, **kw):
        if ctx.window_open() and step > 1:
            step -= 1
        return inner(self, step, like=like, **kw)

    CheckpointLoader.restore = restore
