from benchmark import counts


def test_extend_dah_bytes():
    # ODS read once, EDS written once, 4k roots of 90 bytes.
    assert counts.extend_dah_bytes(512) == 512 * 512 * 512 * 5 + 4 * 512 * 90
    assert counts.extend_dah_bytes(1) == 5 * 512 + 360


def test_sha_compressions():
    assert counts.sha_blocks(55) == 1 and counts.sha_blocks(56) == 2
    n = 2 * 128
    assert counts.sha_compressions(128) == (
        n * n * 9 + 2 * n * (n - 1) * 3 + 2 * n * 2 + (2 * n - 1) * 2)
