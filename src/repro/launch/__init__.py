from repro.launch.mesh import make_test_mesh  # noqa: F401
