"""One module per kind of job, named by a traffic file's ``kind``; see
:mod:`bench.jobs`."""
