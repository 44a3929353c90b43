from benchmark_checks import names_of

globals().update(names_of("test_loop_spans"))
