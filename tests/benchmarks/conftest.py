"""No fixture lives here any more. Until PR 47 this file handed
``test_bench_trinity.py`` the benchmark as its own PR left it, because
that module pinned its cell as the benchmark's LAST entry
(``workloads[-1]``, ``configs[-1]``, ``per_layer[-1]``); its pins now
find their entries by name and hold what their PR appended as a prefix
(``test_bench_mimo.py``'s form), and ``test_bench_room.py`` drives every
such pin with a cell appended behind the last. The file itself stays
only because documents this PR may not edit name its path
(``tests/test_docs.py`` checks that they name files that exist); a PR
that corrects them deletes it (PERF.md, Open questions)."""
