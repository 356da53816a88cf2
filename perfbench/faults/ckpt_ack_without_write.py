"""The control of the checkpoint cell: a step made visible that is not
whole. Inside the window one data file's write is dropped (its bytes
reported as written, checksums as computed), the manifest and the rename go
through."""


def plant(ctx) -> None:
    from tpu3fs.client.file_io import FileIoClient

    inner = FileIoClient.batch_write_files

    def batch_write_files(self, files, **kw):
        if ctx.window_open() and len(files) > 4:
            kept = inner(self, files[:-1], **kw)
            if kw.get("with_checksums"):
                from tpu3fs.storage.types import Checksum

                counts, sums = kept
                last = files[-1][2]
                return (counts + [len(memoryview(last).cast("B"))],
                        sums + Checksum.of_many([last]))
            return kept + [len(memoryview(files[-1][2]).cast("B"))]
        return inner(self, files, **kw)

    FileIoClient.batch_write_files = batch_write_files
