"""An answer altered where it is produced: every block the file client
reads for a batched get comes back with one bit flipped."""


def _flip(buf) -> bytes:
    out = bytearray(buf)
    out[len(out) // 2] ^= 0x40
    return bytes(out)


def plant(ctx) -> None:
    from tpu3fs.client.file_io import FileIoClient

    inner = FileIoClient.batch_read_files

    def batch_read_files(self, files, *a, **kw):
        return [_flip(b) for b in inner(self, files, *a, **kw)]

    FileIoClient.batch_read_files = batch_read_files
