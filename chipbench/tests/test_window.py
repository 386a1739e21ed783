from window import Window, epoch_turns_in_window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def run(step_s, seconds, images=2048):
    clock = FakeClock()
    w = Window(seconds, clock=clock)
    w.open()
    going = w.boundary()
    while going:
        clock.t += step_s
        going = w.boundary(images)
    return w


def test_rate_divides_by_the_time_actually_elapsed():
    w = run(0.93, 20.0)
    assert w.steps == 22                      # the first step to finish at or after 20 s
    assert abs(w.elapsed - 22 * 0.93) < 1e-9
    assert abs(w.images_per_s - 2048 / 0.93) < 1e-6
    # a step more or less changes nothing: another nominal length, the same rate
    assert abs(run(0.93, 20.9).images_per_s - w.images_per_s) < 1e-6


def test_closed_window_counts_nothing_more():
    clock = FakeClock()
    w = Window(1.0, clock=clock)
    assert not w.boundary(5)                  # not open yet
    w.open()
    clock.t += 2.0
    assert not w.boundary(7)
    clock.t += 2.0
    assert not w.boundary(7)
    assert (w.images, w.steps, w.elapsed) == (7, 1, 2.0)


def test_trace_ends_at_a_boundary_inside_the_window():
    clock = FakeClock()
    stopped = []
    w = Window(10.0, clock=clock, trace_seconds=3.0,
               on_trace_end=lambda: stopped.append(clock.t))
    w.open()
    while w.boundary(1):
        clock.t += 0.8
    assert len(stopped) == 1 and abs(stopped[0] - 103.2) < 1e-9
    assert abs(w.traced_s - 3.2) < 1e-9 and w.elapsed >= 10.0


def test_epoch_turn_overs_are_fixed_by_the_starting_step():
    # 48 steps an epoch, the window opens before step 4 (0-based)
    assert epoch_turns_in_window(4, 48, 33) == 0      # 30 s at 0.93 s a step
    assert epoch_turns_in_window(4, 48, 44) == 0
    assert epoch_turns_in_window(4, 48, 45) == 1      # from the 45th step on, always one
    assert epoch_turns_in_window(4, 48, 55) == 1


def test_a_reading_is_taken_at_every_boundary_while_the_window_is_open():
    clock = FakeClock()
    seen = []
    w = Window(2.0, clock=clock, on_boundary=lambda: seen.append(clock.t))
    w.open()
    assert w.boundary()
    for _ in range(3):
        clock.t += 0.9
        w.boundary(10)
    assert not w.boundary(10)          # closed: no further reading
    assert seen == w.marks and len(seen) == 4
