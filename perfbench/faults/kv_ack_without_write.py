"""A put acknowledged that never reached the store: inside the window the
partial-stripe write of every second file is dropped, its length reported
as written."""


def plant(ctx) -> None:
    from tpu3fs.client.file_io import FileIoClient
    from tpu3fs.storage.craq import UpdateReply
    from tpu3fs.utils.result import Code

    inner = FileIoClient._write_ec_chunk
    state = {"n": 0}

    def _write_ec_chunk(self, inode, chain_id, idx, in_off, part, cs):
        state["n"] += 1
        if ctx.window_open() and state["n"] % 2 == 0:
            return UpdateReply(Code.OK)
        return inner(self, inode, chain_id, idx, in_off, part, cs)

    FileIoClient._write_ec_chunk = _write_ec_chunk
